"""Operations and bytes a training step of the latent-attention layer-pattern model
REQUIRES, from shapes alone (the companion of ``counts.py`` and
``counts_hybrid.py``, whose head, dense feed-forward, router and routed-expert
counts it reuses, and of ``counts_windowed.py``, whose causal half square it reuses).

With T = batch * L positions a step, d the width, H heads, a key/value latent of
``c``, head widths ``n`` (no position), ``r`` (rotary) and ``v`` (value), forward
(a training step is 3x):

    latent attention  q     2*T*d*H*(n + r)
                      kv_a  2*T*d*(c + r)          the latent and the ONE rotary key head
                      kv_b  2*T*c*H*(n + v)
                      o     2*T*H*v*d
                      pairs batch * L*(L+1)/2 (the causal half square) * H * 2*(n + r + v):
                            scores contract over n + r, the mix over v
    shared expert     3 * 2*S*d*fs, S the positions the shared experts multiplied: those
                      the program COUNTED (``measured_shared_tokens``: its
                      ``shared_expert_tokens`` counter in the chunk stage log) where a
                      reader has them, else every position (T)
    routed experts    counts_hybrid.moe_forward_flops (router 2*T*d*E; experts at the
                      assignments the program COUNTED, else the even-routing expectation)
    dense feed-forward  counts_hybrid.dense_ffn_forward_flops
    head              counts.head_forward_flops

Embedding gathers, norms, softmax, rotary, sorting and the optimizer are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence

from benchmark import counts, counts_hybrid
from benchmark.counts_windowed import band_pairs
from benchmark.reference.lfm2_moe import layer_kinds

TRAIN_PASSES = counts.TRAIN_PASSES
SCOPE = "latent_attention"


def projection_weights(model: Mapping[str, Any]) -> Dict[str, int]:
    """Parameters of one latent-attention layer's four projections."""
    a, d = model[SCOPE], model["embedding_dim"]
    heads, latent = a["num_heads"], a["kv_latent_dim"]
    nope, rope, value = a["nope_head_dim"], a["rope_head_dim"], a["value_head_dim"]
    return {
        "q": d * heads * (nope + rope), "kv_a": d * (latent + rope),
        "kv_b": latent * heads * (nope + value), "o": heads * value * d,
    }


def attention_forward_flops_by_part(model: Mapping[str, Any], batch_size: int) -> Dict[str, float]:
    """One latent-attention layer: each projection, and the visible pairs."""
    a, t = model[SCOPE], counts.positions(model, batch_size)
    out = {name: 2.0 * t * size for name, size in projection_weights(model).items()}
    width = a["nope_head_dim"] + a["rope_head_dim"] + a["value_head_dim"]
    out["pairs"] = 2.0 * batch_size * band_pairs(model["max_sequence_length"], None) * a["num_heads"] * width
    return out


def measured_shared_tokens(records: Sequence[Mapping[str, Any]]) -> Optional[float]:
    """Positions a step's shared experts multiplied, per sparse layer, as the
    program counted them: the mean over the steps and layers of the chunk records'
    ``counters.shared_expert_tokens`` ([steps, sparse layers]). Nothing where no
    record carries the counter."""
    counted = [
        tokens
        for record in records
        for step in record.get("counters", {}).get("shared_expert_tokens", ())
        for tokens in step
    ]
    return sum(counted) / len(counted) if counted else None


def shared_forward_flops(model, batch_size: int, shared_tokens: Optional[float] = None) -> float:
    """One sparse layer's shared expert at the counted positions (None: all of them)."""
    if shared_tokens is None:
        shared_tokens = counts.positions(model, batch_size)
    width = model["shared_experts"]["shared_expert_dim"]
    return 3 * 2.0 * shared_tokens * model["embedding_dim"] * width


def forward_flops_by_kind(
    model: Mapping[str, Any], batch_size: int, assignments: Optional[float] = None,
    shared_tokens: Optional[float] = None,
) -> Dict[str, float]:
    """Forward FLOPs a step requires under each scope, summed over its layers;
    ``assignments`` and ``shared_tokens``: per sparse layer, as counted."""
    kinds = layer_kinds(model)
    sparse = sum(1 for _, is_sparse in kinds if is_sparse)
    return {
        SCOPE: len(kinds) * sum(attention_forward_flops_by_part(model, batch_size).values()),
        "shared_expert": sparse * shared_forward_flops(model, batch_size, shared_tokens),
        "moe": sparse * counts_hybrid.moe_forward_flops(model, batch_size, assignments),
        "dense_ffn": (len(kinds) - sparse) * counts_hybrid.dense_ffn_forward_flops(model, batch_size),
        "head": counts.head_forward_flops(model, batch_size),
    }


def step_train_flops(
    model: Mapping[str, Any], batch_size: int, assignments: Optional[float] = None,
    shared_tokens: Optional[float] = None,
) -> float:
    """Required FLOPs of one optimizer step (no recomputation counted)."""
    by_kind = forward_flops_by_kind(model, batch_size, assignments, shared_tokens)
    return TRAIN_PASSES * sum(by_kind.values())


def attention_train_bytes(model: Mapping[str, Any], batch_size: int) -> float:
    """Least HBM traffic of the latent-attention layers in a step: per layer the
    hidden states read and their gradient written (compute dtype, 2 bytes), the
    float32 projection kernels read and their gradients written. Neither the
    scores nor the keys broadcast over the heads are in it: a fused route need
    write neither."""
    t, d = counts.positions(model, batch_size), model["embedding_dim"]
    weights = sum(projection_weights(model).values())
    return len(layer_kinds(model)) * (2.0 * t * d * 2 + 2.0 * weights * 4)


def attention_least_seconds(model, batch_size: int, peaks: Mapping[str, Any]):
    """(seconds, which bound holds) for the latent-attention layers' work of one step."""
    flops = TRAIN_PASSES * forward_flops_by_kind(model, batch_size)[SCOPE]
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = attention_train_bytes(model, batch_size) / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")


def scope_ms_per_step(context: Mapping[str, Any], scope: str) -> Optional[float]:
    """Device milliseconds per step under ``scope`` and its transpose in the traced
    slice; nothing where the capture holds no op under it."""
    traced = context["traced"]
    device_s = traced["scope_s"].get(scope, 0.0)
    if device_s <= 0 or traced["steps"] <= 0:
        return None
    return 1e3 * device_s / traced["steps"]
