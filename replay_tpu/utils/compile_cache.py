"""Where executables keep JAX's persistent compilation cache.

Called from entry points only (``chip_smoke.py``, ``benchmark/run.py``,
``python -m replay_tpu.serve.remote``), never on ``import replay_tpu``.
"""

from __future__ import annotations

import os
from pathlib import Path


def enable_compile_cache() -> str:
    """Return the cache directory in force. ``JAX_COMPILATION_CACHE_DIR`` wins
    (JAX reads it itself; nothing is set in code); otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part of the
    cache key and a directory that moves never hits."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
