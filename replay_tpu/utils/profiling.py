"""Profiling hooks (beyond-parity: the reference has none — SURVEY.md §5).

``trace(dir)`` wraps a region in a jax.profiler trace viewable in TensorBoard /
xprof; ``StepTimer`` measures steady-state steps/sec + samples/sec with
block_until_ready fencing and warmup exclusion.

For per-step instantaneous rates, retrace counting and device-memory
telemetry see :mod:`replay_tpu.obs` (``StepTelemetry`` generalizes this
timer and feeds ``Trainer.fit``'s event stream).
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional


@contextlib.contextmanager
def trace(log_dir: str, *, create_perfetto_link: bool = False,
          python_tracer_level: int = 0):
    """Capture a device trace of the enclosed region.

    ``python_tracer_level=0`` (the default) keeps python-frame events OUT of
    the capture: a busy host loop (the scan-chunked fit's feeder + accounting
    threads) emits millions of them, flooding the profiler's event cap and
    dropping the XLA op events a reader of the capture needs. jax's public
    ``start_trace`` pins the level to 1, so when the
    xla_client ProfileOptions API is available the session is driven directly
    (same export layout); otherwise this degrades to the public API.
    """
    import jax

    session = None
    if not create_perfetto_link:
        try:
            from jax._src.lib import xla_client

            options = xla_client.profiler.ProfileOptions()
            options.python_tracer_level = int(python_tracer_level)
            jax.devices()  # TPU: libtpu must initialize BEFORE the tracer
            session = xla_client.profiler.ProfilerSession(options)
        except Exception:
            session = None
    if session is None:
        jax.profiler.start_trace(log_dir, create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        if session is not None:
            session.export(session.stop(), str(log_dir))
        else:
            jax.profiler.stop_trace()


class StepTimer:
    """Steady-state throughput: call ``tick(result)`` once per step."""

    def __init__(self, warmup_steps: int = 3, samples_per_step: Optional[int] = None) -> None:
        self.warmup_steps = warmup_steps
        self.samples_per_step = samples_per_step
        self._count = 0
        self._start: Optional[float] = None

    def tick(self, result=None) -> None:
        self._count += 1
        if self._count == self.warmup_steps:
            if result is not None:
                import jax

                jax.block_until_ready(result)
            self._start = time.perf_counter()

    def finish(self, result=None) -> dict:
        """Steady-state record — shape-stable: always ``steps`` (measured,
        post-warmup), ``steps_per_sec`` and ``samples_per_sec``, NaN-filled
        when nothing was measured, so JSONL consumers never KeyError."""
        if result is not None:
            import jax

            jax.block_until_ready(result)
        measured = self._count - self.warmup_steps
        if self._start is None or measured <= 0:
            return {
                "steps": max(measured, 0),
                "steps_per_sec": float("nan"),
                "samples_per_sec": float("nan"),
            }
        elapsed = time.perf_counter() - self._start
        return {
            "steps": measured,
            "steps_per_sec": measured / elapsed,
            "samples_per_sec": (
                measured * self.samples_per_step / elapsed
                if self.samples_per_step
                else float("nan")
            ),
        }
