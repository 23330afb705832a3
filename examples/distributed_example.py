"""Multi-host data-parallel training — two processes, one job.

Demonstrates the real multi-host path (jax.distributed + gloo on CPU; identical
code targets ICI/DCN on TPU pods): this launcher spawns two worker processes
that join one job via ``initialize_distributed``, feed disjoint batch shards,
and print the (psum-reduced, identical) losses each host observes.

Run: python examples/distributed_example.py
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
WORKER = REPO_ROOT / "tests" / "parallel" / "mp_worker.py"


def main() -> None:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    out_dir = Path(tempfile.mkdtemp())
    env = {
        **os.environ,
        "PYTHONPATH": str(REPO_ROOT),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
    }
    workers = [
        subprocess.Popen(
            [sys.executable, str(WORKER), str(rank), coordinator,
             str(out_dir / f"rank{rank}.json")],
            env=env,
        )
        for rank in range(2)
    ]
    for worker in workers:
        worker.wait(timeout=300)
    for rank in range(2):
        result = json.loads((out_dir / f"rank{rank}.json").read_text())
        print(f"rank {rank}: losses {[round(l, 4) for l in result['losses']]} "
              f"metrics {result['metrics']}")


if __name__ == "__main__":
    main()
