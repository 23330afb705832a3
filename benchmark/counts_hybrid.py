"""Operations and bytes a training step of the layer-pattern model REQUIRES, from
shapes alone (the companion of ``counts.py``, whose head counts it reuses).

With T = batch * length positions a step, d the width, forward (a training step
is 3x: forward, and two products of the same size per matmul on the way back):

    conv mixer        in_proj 2*T*d*3d + out_proj 2*T*d*d      (the k-tap conv
                      and the gates are element-wise: not counted)
    attention mixer   q, o 2 * 2*T*d*(H*hd); k, v 2 * 2*T*d*(Hkv*hd);
                      scores and mix 2 * 2*T*(L/2)*(H*hd)      (causal: half the square)
    dense SwiGLU      3 * 2*T*d*f
    sparse experts    router 2*T*d*E; experts 3 * 2*A*d*fe with A the assignments
                      a step sends to the experts held here: those the program
                      COUNTED (``measured_assignments``: its ``expert_load``
                      counter in the chunk stage log) where a reader has them,
                      else the EXPECTATION under even routing, T*k*held/E. A
                      router that trains without a balance loss does not stay
                      even, and a share of work the step did not do is no share.
                      Whatever implements the layer (a dense mix over the held
                      experts computes T*held rows, and is not owed them)
    head              counts.head_forward_flops

Embedding gathers, norms, softmax, rotary, sorting and the optimizer are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence

from benchmark import counts
from benchmark.reference.lfm2_moe import layer_kinds

TRAIN_PASSES = counts.TRAIN_PASSES


def expected_assignments(model: Mapping[str, Any], batch_size: int) -> float:
    """Assignments a step sends to the experts held here, under even routing."""
    e = model["experts"]
    return (
        counts.positions(model, batch_size) * e["experts_per_token"]
        * e["experts_held"] / e["num_experts"]
    )


def measured_assignments(records: Sequence[Mapping[str, Any]]) -> Optional[float]:
    """Assignments a step sent to the experts held here, per expert layer, as the
    program counted them: the mean over the steps and expert layers of the chunk
    records' ``counters.expert_load`` ([steps, expert layers, held experts]).
    Nothing where no record carries the counter."""
    per_layer = [
        sum(load)
        for record in records
        for step in record.get("counters", {}).get("expert_load", ())
        for load in step
    ]
    return sum(per_layer) / len(per_layer) if per_layer else None


def conv_forward_flops(model, batch_size: int) -> float:
    t, d = counts.positions(model, batch_size), model["embedding_dim"]
    return 2.0 * t * d * 3 * d + 2.0 * t * d * d


def attention_forward_flops(model, batch_size: int) -> float:
    t, d, a = counts.positions(model, batch_size), model["embedding_dim"], model["attention"]
    q_width, kv_width = a["num_heads"] * a["head_dim"], a["num_kv_heads"] * a["head_dim"]
    projections = 2 * 2.0 * t * d * q_width + 2 * 2.0 * t * d * kv_width
    return projections + 2 * 2.0 * t * (model["max_sequence_length"] / 2) * q_width


def dense_ffn_forward_flops(model, batch_size: int) -> float:
    return 3 * 2.0 * counts.positions(model, batch_size) * model["embedding_dim"] * model["ffn_dim"]


def moe_forward_flops(model, batch_size: int, assignments: Optional[float] = None) -> float:
    t, d, e = counts.positions(model, batch_size), model["embedding_dim"], model["experts"]
    router = 2.0 * t * d * e["num_experts"]
    if assignments is None:
        assignments = expected_assignments(model, batch_size)
    return router + 3 * 2.0 * assignments * d * e["expert_dim"]


def forward_flops_by_kind(
    model: Mapping[str, Any], batch_size: int, assignments: Optional[float] = None
) -> Dict[str, float]:
    """Forward FLOPs a step requires, summed over the layers of each kind;
    ``assignments``: per expert layer, as counted (None: the expectation)."""
    out = {"conv": 0.0, "attention": 0.0, "dense_ffn": 0.0, "moe": 0.0}
    for mixer, sparse in layer_kinds(model):
        if mixer == "conv":
            out["conv"] += conv_forward_flops(model, batch_size)
        else:
            out["attention"] += attention_forward_flops(model, batch_size)
        if sparse:
            out["moe"] += moe_forward_flops(model, batch_size, assignments)
        else:
            out["dense_ffn"] += dense_ffn_forward_flops(model, batch_size)
    out["head"] = counts.head_forward_flops(model, batch_size)
    return out


def step_train_flops(
    model: Mapping[str, Any], batch_size: int, assignments: Optional[float] = None
) -> float:
    """Required FLOPs of one optimizer step (no recomputation counted)."""
    return TRAIN_PASSES * sum(forward_flops_by_kind(model, batch_size, assignments).values())


def moe_train_bytes(model: Mapping[str, Any], batch_size: int) -> float:
    """Least HBM traffic of the expert layers in a step: per layer the hidden
    states read and their gradient written (compute dtype, 2 bytes), the float32
    router and held experts' kernels read and their gradients written."""
    t, d, e = counts.positions(model, batch_size), model["embedding_dim"], model["experts"]
    weights = d * e["num_experts"] + 3 * e["experts_held"] * d * e["expert_dim"]
    layers = sum(1 for _, sparse in layer_kinds(model) if sparse)
    return layers * (2.0 * t * d * 2 + 2.0 * weights * 4)


def moe_least_seconds(
    model, batch_size: int, peaks: Mapping[str, Any], assignments: Optional[float] = None
):
    """(seconds, which bound holds) for the expert layers' work of one step."""
    flops = TRAIN_PASSES * forward_flops_by_kind(model, batch_size, assignments)["moe"]
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moe_train_bytes(model, batch_size) / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")
