"""The benchmark's one command: one process, one cell, one run.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell, warms it up, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints the contract's result as
the last line of standard output. With no TPU, or fewer chips than the cell asks
for, it exits non-zero and prints no result.

Everything that belongs to one cell is data found by name in ``BENCHMARK.json``:

    configs[].file                     the configuration as it is run
    benchmark/traffic/<traffic>.json   the mix; its ``kind`` names the driver
    benchmark/drivers/<kind>.py        how a kind is warmed, driven and checked
    benchmark/limits/<workload>.json   the limit of each number compared
    benchmark/metrics/<metric>.py      one reader per per-layer metric: ``read(context)``

This file holds no model's, cell's or metric's name.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()  # before any heavy import: set-up counts from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@dataclass
class Cell:
    """One entry of ``workloads`` with the files it names, and this run's arguments."""

    root: Path
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    started: float = STARTED
    devices: Any = None
    keep_capture: bool = False  # for reading a capture by hand; no option sets it


def _reports(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json ({sorted(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    read = lambda path: json.loads((root / path).read_text())  # noqa: E731
    traffic = read(f"benchmark/traffic/{entry['traffic']}.json")
    if int(traffic["chips"]) != int(entry["chips"]):
        raise SystemExit(f"benchmark: {workload} asks for {entry['chips']} chip(s), its traffic file for {traffic['chips']}")
    return Cell(
        root=root, name=workload, chips=int(entry["chips"]),
        config=read(configs[entry["config"]]["file"]), traffic=traffic,
        limits=read(f"benchmark/limits/{workload}.json"),
        end_to_end=[m["name"] for m in spec["end_to_end"] if _reports(m, workload)],
        per_layer=[m["name"] for m in spec["per_layer"] if _reports(m, workload)],
        units={m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]},
    )


def load_module(root: Path, relative: str):
    """A driver or a metric reader, by its file under the checkout."""
    path = root / relative
    spec = importlib.util.spec_from_file_location(relative.replace("/", ".")[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_devices(chips: int):
    """The accelerator, or no run: there is no CPU path."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX found no TPU (platform {devices[0].platform!r}); there is no CPU path"
        )
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX reports {len(devices)}")
    return devices


def enable_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for every
    program, the sub-second ones too: a cell's second run in a checkout compiles
    nothing."""
    import jax

    from replay_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(cell: Cell) -> Dict[str, Any]:
    """Everything after the look for a chip: drive the cell, read the metrics,
    build the result line. ``cell.devices`` holds the devices to run on."""
    import jax

    driver = load_module(cell.root, f"benchmark/drivers/{cell.traffic['kind']}.py")
    outcome = driver.run(cell)
    context = outcome["context"]
    first = cell.devices[0]
    device = {
        "platform": first.platform, "kind": first.device_kind, "count": len(jax.devices()),
        "memory_peak_bytes": context["memory_peak_bytes"],
    }
    values: Dict[str, Any] = {}
    breakdown = None
    if not cell.trace:
        values = {name: outcome["end_to_end"][name] for name in cell.end_to_end}
    else:
        context["device_kind"] = first.device_kind
        traced = context["traced"] = driver.read_capture(cell, context)
        device["busy_s"], device["window_s"] = traced["busy_s"], traced["window_s"]
        breakdown = {"device_ops": traced["device_ops"], "idle_gaps": traced["idle_gaps"]}
        for name in cell.per_layer:
            value = load_module(cell.root, f"benchmark/metrics/{name}.py").read(context)
            if value is not None:  # a reader that finds nothing to read reports nothing
                values[name] = value
    result: Dict[str, Any] = {
        "correct": bool(outcome["correct"]), "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": {
            name: {"value": float(value), "unit": cell.units[name]}
            for name, value in values.items()
        },
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["notes"] = {
        key: context[key]
        for key in (
            "window_s", "steps", "chunks", "setup_stages_s", "reference_s", "comparison",
            "memory_stats",
        )
        if key in context
    }
    result["checks"] = outcome["checks"]  # last: each number compared beside its limit
    return result


def report(result: Dict[str, Any]) -> None:
    for name, check in result["checks"].items():
        verdict = "ok" if check["value"] <= check["limit"] else "OVER"
        print(f"check {name}: {check['value']:.6g} (limit {check['limit']:.6g}) {verdict}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return parser.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    cell.seed, cell.seconds, cell.trace = args.seed, args.seconds, bool(args.trace)
    cell.devices = find_devices(cell.chips)

    enable_cache()
    report(run_cell(cell))
    return 0


if __name__ == "__main__":
    sys.exit(main())
