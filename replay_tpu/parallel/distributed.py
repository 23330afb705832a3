"""Multi-host runtime initialization.

Capability parity with the reference's distributed seams (SURVEY.md §2.9/§5: a
``torch.distributed`` consumed read-only for rank/world_size, collectives
delegated to NCCL): here the whole backend is ``jax.distributed.initialize`` +
XLA collectives over ICI/DCN — one call per host process, then every
``Mesh``/``psum`` in the framework spans all hosts automatically.

``initialize_distributed()`` is idempotent, no-ops in single-process runs, and
resolves the coordinator from standard env vars (fleet schedulers set them):

* ``REPLAY_TPU_COORDINATOR`` / ``JAX_COORDINATOR_ADDRESS`` — host:port
* ``REPLAY_TPU_NUM_PROCESSES`` / ``JAX_NUM_PROCESSES``
* ``REPLAY_TPU_PROCESS_ID`` / ``JAX_PROCESS_ID``

On TPU pods jax can discover everything from the runtime, so calling with no
env set is also valid there.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger("replay_tpu")

_initialized = False


def _env(*names: str) -> Optional[str]:
    for name in names:
        value = os.environ.get(name)
        if value:
            return value
    return None


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> dict:
    """Join the multi-host job (idempotent). Returns the process layout."""
    global _initialized
    import jax

    coordinator_address = coordinator_address or _env(
        "REPLAY_TPU_COORDINATOR", "JAX_COORDINATOR_ADDRESS"
    )
    num_processes = num_processes or _int_env("REPLAY_TPU_NUM_PROCESSES", "JAX_NUM_PROCESSES")
    process_id = process_id if process_id is not None else _int_env(
        "REPLAY_TPU_PROCESS_ID", "JAX_PROCESS_ID"
    )

    # the flag marks an ACTUAL initialization: a no-op call (no coordinator, not
    # a pod) must not block a later call that does carry a coordinator
    if not _initialized and (coordinator_address is not None or _on_tpu_pod()):
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
        logger.info(
            "joined distributed job: process %d/%d",
            jax.process_index(),
            jax.process_count(),
        )

    return {
        "process_id": jax.process_index(),
        "num_processes": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }


def _int_env(*names: str) -> Optional[int]:
    value = _env(*names)
    return int(value) if value is not None else None


def _on_tpu_pod() -> bool:
    """Heuristic: MULTI-worker TPU runtimes list several worker hostnames —
    single-host setups (including one-chip dev machines) must not initialize."""
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hostnames.split(",") if h.strip()]) > 1 and os.environ.get(
        "JAX_PLATFORMS", ""
    ) not in ("cpu",)


def replicas_info(num_workers: int = 1):
    """The input-sharding identity of this process (after initialization)."""
    from replay_tpu.data.nn.partitioning import ReplicasInfo

    return ReplicasInfo.from_jax(num_workers=num_workers)
