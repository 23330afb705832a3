"""Post-training int8 quantization for serving retrieval — the ladder's rung 2.

The bytes that dominate `CandidatePipeline` retrieval latency are the item
embedding table sweep: exact MIPS reads all ``[I, E]`` f32 rows per
micro-batch, and at 10M items × d=128 that is 5 GB — past a single device's
HBM before the model itself is counted (sub-item-IDs paper's memory-per-item
budget, PAPERS.md). Per-item symmetric int8 quantization cuts the sweep 4×:

* **per-row symmetric scales** — ``scale_i = absmax(row_i) / 127``,
  ``q_i = round(row_i / scale_i)`` as int8. No zero points (symmetric), so
  the dequantized score is ``(queries @ q.T) * scale`` — one multiply per
  score, fused by XLA into the matmul epilogue. Weight-only quantization: the
  int8 rows are up-cast in registers after the (¼-sized) HBM read; queries
  stay full precision.
* **re-rank at full precision** — quantized scores pick the top-C CANDIDATES;
  the pipeline then rescores exactly those C rows against the f32 master
  copy (``MIPSIndex.exact_rescore``) before the re-rank/top-k cut, so
  end-to-end top-k quality is preserved (recall@C ≥ 0.99 is the tested gate,
  ``tests/serve/test_quant.py``) while HBM holds only int8 rows.
* **sharded layout reuse** — a mesh-sharded quantized index keeps the
  CEFusedTP ``[I/n, E]`` row-shard layout (int8 values ``P(axis, None)``,
  scales ``P(axis)``), which is what lets 10M-item tables fit where f32
  cannot (ROADMAP items 4+5).

Training NEVER sees int8 — the :class:`~replay_tpu.nn.loss.CEFused` dtype
check rejects integer tables by name. Quantization here is post-training and
serving-only.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import numpy as np

__all__ = [
    "QuantizedTable",
    "quantization_error",
    "quantize_embeddings",
]


@dataclass(frozen=True)
class QuantizedTable:
    """Per-row symmetrically quantized embedding table.

    ``values`` is the int8 payload ``[I, E]``; ``scales`` the f32 per-row
    dequantization factors ``[I]`` (``row_i ≈ values_i * scales_i``). Rows
    that were entirely zero carry scale 0 and dequantize to exact zeros.
    """

    values: np.ndarray  # int8 [I, E]
    scales: np.ndarray  # f32 [I]

    @property
    def num_items(self) -> int:
        return int(self.values.shape[0])

    @property
    def dim(self) -> int:
        return int(self.values.shape[1])

    @property
    def nbytes(self) -> int:
        """Total payload bytes (int8 values + f32 scales) — the number to
        compare against the f32 table's ``I × E × 4``."""
        return int(self.values.nbytes + self.scales.nbytes)

    def dequantize(self) -> np.ndarray:
        """The f32 approximation ``values * scales[:, None]`` (error ≤
        scale/2 per element — see :func:`quantization_error`)."""
        return self.values.astype(np.float32) * self.scales[:, None]


def quantize_embeddings(table: np.ndarray, bits: int = 8) -> QuantizedTable:
    """Per-item (per-row) symmetric quantization of an ``[I, E]`` f32 table.

    Symmetric (no zero point): ``scale = absmax / qmax`` with ``qmax =
    2^(bits-1) - 1`` (127 for int8), values round-to-nearest. Per-ROW scales
    keep popular high-norm items from crushing the resolution of the long
    tail — the per-tensor alternative loses recall precisely on the rows
    retrieval cares about.
    """
    if bits != 8:
        msg = f"only int8 is supported (bits=8), got bits={bits}"
        raise ValueError(msg)
    table = np.asarray(table, np.float32)
    if table.ndim != 2:
        msg = f"expected an [num_items, embed] table, got shape {table.shape}"
        raise ValueError(msg)
    qmax = float(2 ** (bits - 1) - 1)
    absmax = np.max(np.abs(table), axis=1)  # [I]
    scales = (absmax / qmax).astype(np.float32)
    # zero rows: scale 0 would divide by zero; quantize them to zeros exactly
    safe = np.where(scales > 0.0, scales, 1.0)
    values = np.clip(np.rint(table / safe[:, None]), -qmax, qmax).astype(np.int8)
    values[scales == 0.0] = 0
    return QuantizedTable(values=values, scales=scales)


def quantization_error(table: np.ndarray, quantized: QuantizedTable) -> Dict[str, Any]:
    """Round-trip error stats: per-element absolute error is bounded by
    ``scale/2`` (round-to-nearest of a symmetric grid); the record carries the
    observed max against that bound plus the relative Frobenius error."""
    table = np.asarray(table, np.float32)
    approx = quantized.dequantize()
    abs_err = np.abs(approx - table)
    bound = quantized.scales[:, None] / 2.0
    denom = float(np.linalg.norm(table)) or 1.0
    return {
        "max_abs_error": float(abs_err.max(initial=0.0)),
        "max_error_to_bound": float(
            np.max(abs_err / np.maximum(bound, 1e-12), initial=0.0)
        ),
        "rel_frobenius_error": float(np.linalg.norm(approx - table)) / denom,
        "bytes_f32": int(table.nbytes),
        "bytes_int8": quantized.nbytes,
        "bytes_ratio": quantized.nbytes / max(int(table.nbytes), 1),
    }
