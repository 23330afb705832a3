"""Operations and bytes a training step of the looped layer-pattern model REQUIRES,
from shapes alone (the companion of ``counts.py``, whose head it reuses, and of
``counts_windowed.py``, whose causal half square it reuses).

With P = batch * L positions a step, d the width, H query and G key/value heads
of width ``hd``, f the feed-forward width, N layers run T times (``loop_steps``)
and I the items scored, forward (a training step is 3x):

    one layer application   projections  2*P*d*hd*(2H + 2G)     q, o and k, v
                            pairs        batch * L*(L+1)/2 (the causal half square)
                                         * H * 2*(hd + hd): scores and mix
                            feed-forward 3 * 2*P*d*f
    the stack               T * N applications
    an exit                 head 2*P*d*I (counts.head_forward_flops) + gate 2*P*d
    the exits               T of them

Recomputation is NOT counted: a program that recomputes each block application on
the way back does a fourth pass of the stack's forward that no count here owes, so
a share of the stack's roofline under full recomputation is at most 3/4. Embedding
gathers, norms, softmax, rotary and the optimizer are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from benchmark import counts
from benchmark.counts_windowed import band_pairs

TRAIN_PASSES = counts.TRAIN_PASSES
GROUP = "loop"


def applications(model: Mapping[str, Any]) -> int:
    """Block applications a step: layers times passes."""
    return len(model["layers"]["layer_types"]) * model[GROUP]["loop_steps"]


def layer_weights(model: Mapping[str, Any]) -> int:
    """The matmul weights of one layer: the four projections and the SwiGLU."""
    a, d = model["attention"], model["embedding_dim"]
    heads, kv_heads, width = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    return d * width * (2 * heads + 2 * kv_heads) + 3 * d * model["ffn_dim"]


def application_forward_flops(model: Mapping[str, Any], batch_size: int) -> float:
    """One layer application: its weights at every position, and the visible pairs."""
    a = model["attention"]
    pairs = batch_size * band_pairs(model["max_sequence_length"], None) * a["num_heads"] * 2 * 2 * a["head_dim"]
    return 2.0 * counts.positions(model, batch_size) * layer_weights(model) + pairs


def exit_forward_flops(model: Mapping[str, Any], batch_size: int) -> float:
    """One exit: the full-catalog head and the gate."""
    gate = 2.0 * counts.positions(model, batch_size) * model["embedding_dim"]
    return counts.head_forward_flops(model, batch_size) + gate


def forward_flops_by_part(model: Mapping[str, Any], batch_size: int) -> Dict[str, float]:
    return {
        "recurrence": applications(model) * application_forward_flops(model, batch_size),
        "exits": model[GROUP]["loop_steps"] * exit_forward_flops(model, batch_size),
    }


def step_train_flops(model: Mapping[str, Any], batch_size: int) -> float:
    """Required FLOPs of one optimizer step (no recomputation counted)."""
    return TRAIN_PASSES * sum(forward_flops_by_part(model, batch_size).values())


def recurrence_train_bytes(model: Mapping[str, Any], batch_size: int) -> float:
    """Least HBM traffic of the stack in a step: per application the stream read
    and its gradient written (compute dtype, 2 bytes); per layer the float32
    weights read and their gradients written once."""
    p, d = counts.positions(model, batch_size), model["embedding_dim"]
    layers = len(model["layers"]["layer_types"])
    return applications(model) * 2.0 * p * d * 2 + layers * 2.0 * layer_weights(model) * 4


def exit_heads_train_bytes(model: Mapping[str, Any], batch_size: int) -> float:
    """Least HBM traffic of the exits in a step: per exit the hidden states read and
    their gradient written (2 bytes) and the labels read; the float32 output table
    read and its gradient written once. No logits: a head need not write them."""
    p, d = counts.positions(model, batch_size), model["embedding_dim"]
    steps = model[GROUP]["loop_steps"]
    return steps * (2.0 * p * d * 2 + p * 4.0) + 2.0 * model["num_items"] * d * 4


def _least(flops: float, nbytes: float, peaks: Mapping[str, Any]):
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")


def recurrence_least_seconds(model, batch_size: int, peaks: Mapping[str, Any]):
    """(seconds, which bound holds) for the T passes of the stack in one step."""
    flops = TRAIN_PASSES * forward_flops_by_part(model, batch_size)["recurrence"]
    return _least(flops, recurrence_train_bytes(model, batch_size), peaks)


def exit_heads_least_seconds(model, batch_size: int, peaks: Mapping[str, Any]):
    """(seconds, which bound holds) for the T exits (heads and gates) in one step."""
    flops = TRAIN_PASSES * forward_flops_by_part(model, batch_size)["exits"]
    return _least(flops, exit_heads_train_bytes(model, batch_size), peaks)
