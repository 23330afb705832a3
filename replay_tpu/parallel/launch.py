"""Process-real worker launching for multi-host training on one machine.

The rest of :mod:`replay_tpu.parallel` assumes ``jax.distributed`` has been
initialized; this module starts the actual OS processes. One launcher call
starts N python workers, each a real ``jax.distributed`` rank (gloo CPU
collectives under tests; the same worker scripts run unchanged on TPU pods
where the runtime provides the coordinator), and supervises them to
completion:

* **Coordinator handshake (no fixed ports):** the launcher binds an ephemeral
  port for the jax.distributed coordinator and publishes it to every worker
  via the standard env vars ``initialize_distributed`` already resolves
  (``REPLAY_TPU_COORDINATOR`` / ``REPLAY_TPU_NUM_PROCESSES`` /
  ``REPLAY_TPU_PROCESS_ID``) — two launchers on one host can never collide.
  The same address is also passed as argv for workers that predate the env
  contract.

* **Peer-death supervision:** collectives hang forever when a peer dies —
  a SIGKILLed rank leaves every survivor blocked inside gloo with no error.
  The launcher polls; once any worker exits (cleanly or by signal), the
  remaining workers get ``grace_s`` to finish on their own, then are
  SIGKILLed and reported with ``reaped=True``. A chaos test therefore always
  gets its processes back: the victim's real ``-SIGKILL`` returncode AND the
  survivors' reaped state, never a hung pytest.

* **No pipe deadlocks:** worker stdout/stderr spool to temp files (a worker
  logging megabytes can never fill a pipe and block mid-collective).

``launch_workers`` is the harness behind ``tests/parallel/test_multiprocess``
and the multi-process leg of ``__graft_entry__.dryrun_multichip``;
``clean_cpu_env`` builds the per-worker environment (forced CPU platform, N
virtual devices per process, gloo collectives).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

logger = logging.getLogger("replay_tpu")

__all__ = ["WorkerResult", "LaunchError", "free_port", "clean_cpu_env", "launch_workers"]


def free_port(host: str = "127.0.0.1") -> int:
    """An ephemeral port chosen by the OS — callers bind-and-release, then
    hand the number to a child that binds it for real. The tiny race this
    leaves is why every consumer here also tolerates a failed bind loudly."""
    with socket.socket() as sock:
        sock.bind((host, 0))
        return sock.getsockname()[1]


def clean_cpu_env(
    local_devices: int = 4,
    repo_root: Optional[str] = None,
    extra: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """The environment of a CPU worker process: the platform forced to CPU
    (a worker must never contend for a chip its parent holds) with
    ``local_devices`` virtual devices, and gloo selected for CPU collectives.
    """
    root = str(repo_root) if repo_root is not None else str(Path.cwd())
    env = {
        **os.environ,
        "PYTHONPATH": root,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={local_devices}",
        "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
    }
    env.update(extra or {})
    return env


@dataclasses.dataclass
class WorkerResult:
    """One worker's outcome: its rank, how it exited, and what it printed.

    With ``launch_workers(run_dir=...)``, ``artifacts_dir`` names the rank's
    persisted forensic directory (``<run_dir>/workers/rank<i>/``: full
    stdout/stderr spools + ``meta.json`` on abnormal exit, and the worker's
    flight ring if it recorded one) and ``flight_path`` the ring path the
    worker was handed — ``None`` without a run_dir."""

    rank: int
    returncode: Optional[int]
    stdout: str
    stderr: str
    reaped: bool = False  # launcher had to SIGKILL it after a peer died/hung
    artifacts_dir: Optional[str] = None
    flight_path: Optional[str] = None

    @property
    def killed_by(self) -> Optional[int]:
        """The signal number that killed the worker, or ``None``."""
        if self.returncode is not None and self.returncode < 0:
            return -self.returncode
        return None


class LaunchError(RuntimeError):
    """Raised (``check=True``) when any worker exits nonzero or is reaped."""


def launch_workers(
    script: str,
    num_processes: int,
    args_for: Optional[Callable[[int], Sequence[str]]] = None,
    env: Optional[Dict[str, str]] = None,
    timeout: float = 300.0,
    grace_s: float = 20.0,
    check: bool = True,
    pass_rank_argv: bool = True,
    python: str = sys.executable,
    run_dir: Optional[str] = None,
) -> List[WorkerResult]:
    """Run ``num_processes`` copies of ``script`` as one distributed job.

    Each worker gets the coordinator handshake via env
    (``REPLAY_TPU_COORDINATOR``/``REPLAY_TPU_NUM_PROCESSES``/
    ``REPLAY_TPU_PROCESS_ID``) and — with ``pass_rank_argv`` — as leading
    argv ``<rank> <host:port>``, followed by ``args_for(rank)``.

    Supervision: after the first worker exit, survivors get ``grace_s``
    seconds (a peer's death wedges gloo collectives — waiting longer only
    hangs the caller), then are SIGKILLed with ``reaped=True``. ``timeout``
    bounds the whole job the same way. With ``check=True`` any nonzero or
    reaped worker raises :class:`LaunchError` carrying the stderr tails;
    chaos callers pass ``check=False`` and assert on the results directly.

    ``run_dir`` turns the launch forensic: every rank is handed a flight-ring
    path (``REPLAY_TPU_FLIGHT_PATH`` → ``<run_dir>/workers/rank<i>/
    flight.ring``, which ``Trainer.fit`` picks up with no worker change —
    the worker's last records survive its SIGKILL in the ring), and on
    abnormal exit (nonzero, signaled, or reaped) the rank's FULL stdout/
    stderr spools plus a ``meta.json`` (returncode, ``killed_by``, reaped)
    are persisted next to it — the artifacts CI uploads and
    ``obs.report --postmortem`` merges. A :class:`LaunchError` then names
    the persisted paths instead of only quoting stderr tails.
    """
    if num_processes < 1:
        msg = f"num_processes must be >= 1, got {num_processes}"
        raise ValueError(msg)
    coordinator = f"127.0.0.1:{free_port()}"
    base_env = dict(env if env is not None else os.environ)
    rank_dirs: List[Optional[Path]] = [None] * num_processes
    if run_dir is not None:
        for rank in range(num_processes):
            rank_dirs[rank] = Path(run_dir) / "workers" / f"rank{rank}"
            rank_dirs[rank].mkdir(parents=True, exist_ok=True)
    spools = []
    workers: List[subprocess.Popen] = []
    try:
        for rank in range(num_processes):
            worker_env = {
                **base_env,
                "REPLAY_TPU_COORDINATOR": coordinator,
                "REPLAY_TPU_NUM_PROCESSES": str(num_processes),
                "REPLAY_TPU_PROCESS_ID": str(rank),
            }
            if rank_dirs[rank] is not None:
                worker_env["REPLAY_TPU_FLIGHT_PATH"] = str(
                    rank_dirs[rank] / "flight.ring"
                )
            argv = [python, str(script)]
            if pass_rank_argv:
                argv += [str(rank), coordinator]
            argv += [str(a) for a in (args_for(rank) if args_for else ())]
            out = tempfile.TemporaryFile()
            err = tempfile.TemporaryFile()
            spools.append((out, err))
            workers.append(
                subprocess.Popen(argv, env=worker_env, stdout=out, stderr=err)
            )

        reaped = [False] * num_processes
        deadline = time.monotonic() + timeout
        first_exit_at: Optional[float] = None
        while any(w.poll() is None for w in workers):
            now = time.monotonic()
            exited = [w for w in workers if w.poll() is not None]
            if exited and first_exit_at is None:
                first_exit_at = now
            hung_past_grace = first_exit_at is not None and now - first_exit_at > grace_s
            if now > deadline or hung_past_grace:
                reason = "timeout" if now > deadline else (
                    f"peer exited {grace_s:.0f}s ago; collectives are wedged"
                )
                for rank, worker in enumerate(workers):
                    if worker.poll() is None:
                        logger.warning(
                            "launch_workers: reaping rank %d (%s)", rank, reason
                        )
                        worker.send_signal(signal.SIGKILL)
                        reaped[rank] = True
                for worker in workers:
                    worker.wait(timeout=30)
                break
            time.sleep(0.1)

        results = []
        for rank, (worker, (out, err)) in enumerate(zip(workers, spools)):
            worker.wait(timeout=30)
            out.seek(0)
            err.seek(0)
            rank_dir = rank_dirs[rank]
            result = WorkerResult(
                rank=rank,
                returncode=worker.returncode,
                stdout=out.read().decode(errors="replace"),
                stderr=err.read().decode(errors="replace"),
                reaped=reaped[rank],
            )
            if rank_dir is not None:
                result.flight_path = str(rank_dir / "flight.ring")
                if result.returncode != 0 or result.reaped:
                    result.artifacts_dir = str(
                        _persist_worker_artifacts(rank_dir, result)
                    )
            results.append(result)
    finally:
        for worker in workers:  # never leak a live worker past the call
            if worker.poll() is None:
                worker.kill()
                worker.wait(timeout=30)
        for out, err in spools:
            out.close()
            err.close()

    if check:
        bad = [r for r in results if r.returncode != 0 or r.reaped]
        if bad:
            details = "\n".join(
                f"rank {r.rank}: returncode={r.returncode} reaped={r.reaped}"
                + (f" artifacts={r.artifacts_dir}" if r.artifacts_dir else "")
                + f"\n{r.stderr[-2000:]}"
                for r in bad
            )
            msg = f"{len(bad)}/{num_processes} workers failed:\n{details}"
            raise LaunchError(msg)
    return results


def _persist_worker_artifacts(rank_dir: Path, result: WorkerResult) -> Path:
    """Write a dead worker's full spools + exit metadata into its rank dir.

    The in-memory :class:`WorkerResult` dies with the test process; CI (and
    ``obs.report --postmortem``) need the evidence on disk next to the flight
    ring. Full spools — the 2000-char stderr tail in :class:`LaunchError` is
    for humans reading an exception, not for forensics."""
    (rank_dir / "stdout.log").write_text(result.stdout, errors="replace")
    (rank_dir / "stderr.log").write_text(result.stderr, errors="replace")
    meta = {
        "rank": result.rank,
        "returncode": result.returncode,
        "killed_by": result.killed_by,
        "reaped": result.reaped,
    }
    (rank_dir / "meta.json").write_text(json.dumps(meta, indent=2) + "\n")
    return rank_dir
