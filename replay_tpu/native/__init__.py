"""Native (C++) kernels for the host-side input pipeline.

The compute path is JAX/XLA; this package holds the runtime pieces the reference
implements natively (its ragged-column dataloader kernels ride torch's C++ —
SURVEY.md §2.8). The extension builds on first use with the in-image g++ via a
direct compiler invocation (no pip). The artifact's file name carries a hash of
``ragged.cpp``, so what is loaded was always built from the source in the tree;
``gather_pad`` falls back to a numpy implementation (and says so at WARNING)
when the build is unavailable.
"""

from __future__ import annotations

import hashlib
import importlib.util
import logging
import os
import subprocess
import sysconfig
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger("replay_tpu")

_HERE = Path(__file__).parent
_SOURCE = _HERE / "ragged.cpp"
_native = None
_build_attempted = False  # one load-or-build attempt per process


def _artifact_path() -> Path:
    """``_ragged_<hash>.so``: keyed on the source bytes and the interpreter ABI,
    so a binary left behind by another version of either is never loaded."""
    key = _SOURCE.read_bytes() + str(sysconfig.get_config_var("SOABI")).encode()
    return _HERE / f"_ragged_{hashlib.sha256(key).hexdigest()[:16]}.so"


def _build(target: Path) -> bool:
    """Compile ragged.cpp into ``target``: built into a per-process staging file
    and renamed into place, so a concurrent reader never sees a partial binary."""
    staging = target.with_suffix(f".{os.getpid()}.building")
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        f"-I{sysconfig.get_paths()['include']}",
        str(_SOURCE),
        "-o", str(staging),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        staging.replace(target)
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as error:
        logger.warning("native ragged kernel build failed (%s); using numpy fallback", error)
        staging.unlink(missing_ok=True)
        return False
    for stale in _HERE.glob("_ragged*.so"):
        if stale != target:
            stale.unlink(missing_ok=True)
    return True


def _load(target: Path) -> Optional[object]:
    spec = importlib.util.spec_from_file_location("replay_tpu.native._ragged", target)
    module = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(module)
    except ImportError as error:
        logger.warning("native ragged kernel failed to load (%s); using numpy fallback", error)
        return None
    return module


def native_available() -> bool:
    global _native, _build_attempted
    if _native is None and not _build_attempted:
        _build_attempted = True
        target = _artifact_path()
        if target.exists() or _build(target):
            _native = _load(target)
    return _native is not None


def native_artifact() -> Optional[Path]:
    """Path of the loaded extension, or None on the numpy fallback."""
    return Path(_native.__file__) if native_available() else None


def gather_pad(
    values: np.ndarray,
    offsets: np.ndarray,
    indices: np.ndarray,
    max_len: int,
    pad_value,
) -> Tuple[np.ndarray, np.ndarray]:
    """Gather ragged rows into a LEFT-padded [batch, max_len] array + mask.

    Integer list columns take the native int64 kernel; floating columns use the
    float64-reinterpret trick (same byte width, same kernel) so values round-trip
    exactly. Rows longer than ``max_len`` keep their last ``max_len`` values
    (recency window — the same truncation the windowless SequenceBatcher applies).
    """
    values = np.asarray(values)
    offsets = np.ascontiguousarray(offsets, np.int64)
    indices = np.ascontiguousarray(indices, np.int64)
    batch = len(indices)
    floating = np.issubdtype(values.dtype, np.floating)
    mask = np.empty((batch, max_len), np.uint8)
    if native_available():
        if floating:
            # reinterpret float64 bit patterns as int64: memcpy semantics only
            payload = np.ascontiguousarray(values, np.float64).view(np.int64)
            pad_bits = np.float64(pad_value).view(np.int64)
            out = np.empty((batch, max_len), np.int64)
            _native.gather_pad_i64(payload, offsets, indices, out, mask, max_len, int(pad_bits))
            return out.view(np.float64), mask.astype(bool)
        payload = np.ascontiguousarray(values, np.int64)
        out = np.empty((batch, max_len), np.int64)
        _native.gather_pad_i64(payload, offsets, indices, out, mask, max_len, int(pad_value))
        return out, mask.astype(bool)
    # numpy fallback: same semantics + validation as the C kernel
    n_rows = len(offsets) - 1
    if ((indices < 0) | (indices >= n_rows)).any():
        msg = "gather_pad: row index out of range"
        raise ValueError(msg)
    starts, stops = offsets[indices], offsets[indices + 1]
    if ((starts < 0) | (stops < starts) | (stops > len(values))).any():
        msg = "gather_pad: offsets out of range"
        raise ValueError(msg)
    out = np.full((batch, max_len), pad_value, np.float64 if floating else np.int64)
    mask[:] = 0
    for b, row in enumerate(indices):
        start, stop = offsets[row], offsets[row + 1]
        if stop - start > max_len:
            start = stop - max_len
        row_values = values[start:stop]
        out[b, max_len - len(row_values):] = row_values
        mask[b, max_len - len(row_values):] = 1
    return out, mask.astype(bool)


def gather_pad_2d(
    values: np.ndarray,
    offsets: np.ndarray,
    indices: np.ndarray,
    max_len: int,
    width: int,
    pad_value,
) -> Tuple[np.ndarray, np.ndarray]:
    """Gather ragged rows of fixed-width vectors into [batch, max_len, width].

    The Array2D (list-of-list) column gather: ``values`` is the [total_steps,
    width] matrix of inner vectors, ``offsets`` index STEPS per row. LEFT-padded
    along the step axis with ``pad_value``; mask is per step. Same dtype rules
    as :func:`gather_pad` (float64 reinterpret for floating columns).
    """
    values = np.asarray(values).reshape(-1, width)
    offsets = np.ascontiguousarray(offsets, np.int64)
    indices = np.ascontiguousarray(indices, np.int64)
    batch = len(indices)
    floating = np.issubdtype(values.dtype, np.floating)
    mask = np.empty((batch, max_len), np.uint8)
    if native_available():
        if floating:
            payload = np.ascontiguousarray(values, np.float64).view(np.int64)
            pad_bits = np.float64(pad_value).view(np.int64)
            out = np.empty((batch, max_len, width), np.int64)
            _native.gather_pad_2d_i64(
                payload, offsets, indices, out, mask, max_len, width, int(pad_bits)
            )
            return out.view(np.float64), mask.astype(bool)
        payload = np.ascontiguousarray(values, np.int64)
        out = np.empty((batch, max_len, width), np.int64)
        _native.gather_pad_2d_i64(
            payload, offsets, indices, out, mask, max_len, width, int(pad_value)
        )
        return out, mask.astype(bool)
    # numpy fallback: same semantics + validation as the C kernel
    n_rows = len(offsets) - 1
    if ((indices < 0) | (indices >= n_rows)).any():
        msg = "gather_pad_2d: row index out of range"
        raise ValueError(msg)
    starts, stops = offsets[indices], offsets[indices + 1]
    if ((starts < 0) | (stops < starts) | (stops > len(values))).any():
        msg = "gather_pad_2d: offsets out of range"
        raise ValueError(msg)
    out = np.full(
        (batch, max_len, width), pad_value, np.float64 if floating else np.int64
    )
    mask[:] = 0
    for b, row in enumerate(indices):
        start, stop = offsets[row], offsets[row + 1]
        if stop - start > max_len:
            start = stop - max_len
        steps = values[start:stop]
        out[b, max_len - len(steps):] = steps
        mask[b, max_len - len(steps):] = 1
    return out, mask.astype(bool)


def gather_pad_spans(
    values: np.ndarray,
    offsets: np.ndarray,
    rows: np.ndarray,
    starts: np.ndarray,
    stops: np.ndarray,
    max_len: int,
    pad_value,
) -> Tuple[np.ndarray, np.ndarray]:
    """Gather (row, start, stop) SPANS of a ragged column, LEFT-padded.

    The windowed-training gather: entry ``b`` takes row ``rows[b]``'s values
    ``[starts[b]:stops[b]]`` (row-relative). Spans longer than ``max_len`` keep
    their last ``max_len`` values. Same dtype rules as :func:`gather_pad`.
    """
    values = np.asarray(values)
    offsets = np.ascontiguousarray(offsets, np.int64)
    rows = np.ascontiguousarray(rows, np.int64)
    starts = np.ascontiguousarray(starts, np.int64)
    stops = np.ascontiguousarray(stops, np.int64)
    batch = len(rows)
    floating = np.issubdtype(values.dtype, np.floating)
    mask = np.empty((batch, max_len), np.uint8)
    if native_available():
        if floating:
            payload = np.ascontiguousarray(values, np.float64).view(np.int64)
            pad_bits = np.float64(pad_value).view(np.int64)
            out = np.empty((batch, max_len), np.int64)
            _native.gather_pad_spans_i64(
                payload, offsets, rows, starts, stops, out, mask, max_len, int(pad_bits)
            )
            return out.view(np.float64), mask.astype(bool)
        payload = np.ascontiguousarray(values, np.int64)
        out = np.empty((batch, max_len), np.int64)
        _native.gather_pad_spans_i64(
            payload, offsets, rows, starts, stops, out, mask, max_len, int(pad_value)
        )
        return out, mask.astype(bool)
    # numpy fallback with the SAME validation + error type as the C kernel
    n_rows = len(offsets) - 1
    row_lengths = offsets[rows.clip(0, n_rows - 1) + 1] - offsets[rows.clip(0, n_rows - 1)]
    bad = (
        (rows < 0) | (rows >= n_rows) | (starts < 0) | (stops < starts) | (stops > row_lengths)
    )
    if bad.any():
        msg = "gather_pad_spans: index or span out of range"
        raise ValueError(msg)
    out = np.full((batch, max_len), pad_value, np.float64 if floating else np.int64)
    mask[:] = 0
    for b in range(batch):
        base = offsets[rows[b]]
        start, stop = int(starts[b]), int(stops[b])
        if stop - start > max_len:
            start = stop - max_len
        span = values[base + start : base + stop]
        out[b, max_len - len(span):] = span
        mask[b, max_len - len(span):] = 1
    return out, mask.astype(bool)
