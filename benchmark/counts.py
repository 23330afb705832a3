"""Operations and bytes a training step REQUIRES, from shapes alone.

Nothing here asks the compiler (``cost_analysis`` counts recomputation and cannot
see a Pallas call) and nothing assumes a device: the peaks come from
``peaks.json`` keyed by the ``device_kind`` JAX reports, and an unknown kind is an
error. Whatever implements a layer, the work counted for it stays the same.

With T = batch * length positions a step, d the hidden size, f the FFN width, L
the length and I the items scored (forward; a training step is 3x: forward, and
two products of the same size for each matmul on the way back):

    head            2*T*d*I
    one block       q,k,v,o projections 4 * 2*T*d*d
                    scores and mix      2 * 2*T*L*d   (the full L x L square)
                    FFN                 2 * 2*T*d*f

Embedding gathers, norms, softmax, dropout and the optimizer are not counted.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Mapping

TRAIN_PASSES = 3  # forward + two matmuls of the same size per matmul backward


def load_peaks(device_kind: str) -> Dict[str, Any]:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json ({sorted(table)}): add its "
            "published peaks with their source, never a default"
        )
    return table[device_kind]


def positions(model: Mapping[str, Any], batch_size: int) -> int:
    return batch_size * model["max_sequence_length"]


def head_forward_flops(model: Mapping[str, Any], batch_size: int) -> float:
    return 2.0 * positions(model, batch_size) * model["embedding_dim"] * model["num_items"]


def blocks_forward_flops(model: Mapping[str, Any], batch_size: int) -> float:
    t, d = positions(model, batch_size), model["embedding_dim"]
    length, f = model["max_sequence_length"], model["ffn_dim"]
    per_block = 4 * 2.0 * t * d * d + 2 * 2.0 * t * length * d + 2 * 2.0 * t * d * f
    return model["num_blocks"] * per_block


def head_train_flops(model: Mapping[str, Any], batch_size: int) -> float:
    return TRAIN_PASSES * head_forward_flops(model, batch_size)


def step_train_flops(model: Mapping[str, Any], batch_size: int) -> float:
    """Required FLOPs of one optimizer step (no recomputation counted)."""
    return TRAIN_PASSES * (
        head_forward_flops(model, batch_size) + blocks_forward_flops(model, batch_size)
    )


def head_train_bytes(model: Mapping[str, Any], batch_size: int) -> float:
    """Least HBM traffic of the loss head in a step: the hidden states read and
    their gradient written (compute dtype, 2 bytes), the float32 item table read
    and its gradient written, the int32 labels read. Logits are not in it: a
    head need not write them."""
    t, d = positions(model, batch_size), model["embedding_dim"]
    return 2.0 * t * d * 2 + 2.0 * model["num_items"] * d * 4 + t * 4.0


def head_least_seconds(model: Mapping[str, Any], batch_size: int, peaks: Mapping[str, Any]):
    """(seconds, which bound holds) for the head's work of one step on one chip."""
    by_flops = head_train_flops(model, batch_size) / peaks["bf16_flops_per_s"]
    by_bytes = head_train_bytes(model, batch_size) / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")
