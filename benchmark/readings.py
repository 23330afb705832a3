"""Reads, in ONE process, the numbers a training cell's limits are set from.

    python benchmark/readings.py --workload <name> --seeds 12 --control-seeds 3 --out <file>

For each seed the timed path's first chunk (the same ``drive_first_chunk`` that
``run.py`` drives, no window) against the plain reference: the LOWER readings.
For the first ``--control-seeds`` of them, the reference put in the program's
place in float8 (the control) and with each planted fault: the UPPER readings.
Not part of a benchmark run; PERF.md gives the readings each limit was set from.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

FAULTS = {"control_fp8": {"precision": "fp8"}, "fault_half_batch": {"fault": "half_batch"}}


def read_seed(cell, driver, seed: int, with_controls: bool):
    built = driver.build(cell, seed)
    traffic, trainer = cell.traffic, built["trainer"]
    stream = driver.Stream(
        built["batcher"], built["transform"],
        bool(cell.config["program"]["transform_takes_key"]), seed, int(traffic["scan_chunk"]),
    )
    fit = partial(trainer.fit, epochs=1, scan_chunk=int(traffic["scan_chunk"]),
                  device_feed=bool(traffic["device_feed"]), log_every=0)
    state, program = driver.drive_first_chunk(cell, built, stream, fit)
    kept = stream.kept
    del state, trainer, fit, stream
    built["trainer"] = built["batcher"] = None
    gc.collect()
    reference = driver.follow_reference(cell, built, kept, seed)
    out = {"program": driver.numbers(program, reference)}
    if with_controls:
        for name, how in FAULTS.items():
            out[name] = driver.numbers(driver.follow_reference(cell, built, kept, seed, **how), reference)
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=1000)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()

    import jax

    from benchmark import run as bench_run

    bench_run.enable_cache()
    cell = bench_run.load_cell(ROOT, args.workload)
    cell.devices = jax.devices()
    driver = bench_run.load_module(ROOT, f"benchmark/drivers/{cell.traffic['kind']}.py")
    record = {"workload": args.workload, "platform": cell.devices[0].platform,
              "kind": cell.devices[0].device_kind, "seeds": {}}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        started = time.perf_counter()
        reading = read_seed(cell, driver, seed, i < args.control_seeds)
        record["seeds"][str(seed)] = reading
        for who, compared in reading.items():
            line = " ".join(f"{k}={v:.3g}" for k, v in compared["numbers"].items())
            print(f"seed {seed} {who}: {line} | {compared['detail']}", flush=True)
        print(f"seed {seed}: {time.perf_counter() - started:.1f} s", flush=True)
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(record, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
