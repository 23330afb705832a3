"""Operations and bytes a training step of the window-and-full layer-pattern model
REQUIRES, from shapes alone (the companion of ``counts.py`` and
``counts_hybrid.py``, whose head, router and expert counts it reuses).

With T = batch * L positions a step, d the width, H query and Hkv key/value heads
of width hd, forward (a training step is 3x):

    attention mixer   q, o 2 * 2*T*d*(H*hd); k, v 2 * 2*T*d*(Hkv*hd)   the TRUE head counts
                      scores and mix 2 * 2*pairs*(H*hd) a row, with ``pairs`` the
                      (query, key) pairs the layer's mask lets through:
                        full_attention      L*(L+1)/2        the causal half square
                        sliding_attention   w*L - w*(w-1)/2  w = min(window, L): the band
    sparse experts    counts_hybrid.moe_forward_flops (router 2*T*d*E; experts at the
                      assignments the program COUNTED, else the even-routing expectation)
    head              counts.head_forward_flops

A route that masks the band instead of skipping it is owed the band all the same.
Embedding gathers, norms, softmax, rotary, sorting and the optimizer are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

from benchmark import counts, counts_hybrid

TRAIN_PASSES = counts.TRAIN_PASSES
SCOPES = {"sliding_attention": "window_attention", "full_attention": "attention"}


def band_pairs(length: int, window: Optional[int]) -> int:
    """(query, key) pairs with 0 <= i - j < window among ``length`` positions;
    ``window`` None: the causal half square."""
    reach = length if window is None else min(window, length)
    return reach * length - reach * (reach - 1) // 2


def visible_pairs(model: Mapping[str, Any], kind: str) -> int:
    window = model["attention"]["sliding_window"] if kind == "sliding_attention" else None
    return band_pairs(model["max_sequence_length"], window)


def layers_of(model: Mapping[str, Any], kind: str) -> int:
    return sum(1 for mixer in model["layers"]["layer_types"] if mixer == kind)


def attention_weights(model: Mapping[str, Any]) -> int:
    """Parameters of one attention layer's four projections."""
    a = model["attention"]
    return 2 * model["embedding_dim"] * (a["num_heads"] + a["num_kv_heads"]) * a["head_dim"]


def attention_forward_flops(model: Mapping[str, Any], batch_size: int, kind: str) -> float:
    """One layer of ``kind``: its projections and its visible pairs."""
    q_width = model["attention"]["num_heads"] * model["attention"]["head_dim"]
    projections = 2.0 * counts.positions(model, batch_size) * attention_weights(model)
    return projections + 2 * 2.0 * batch_size * visible_pairs(model, kind) * q_width


def forward_flops_by_scope(
    model: Mapping[str, Any], batch_size: int, assignments: Optional[float] = None
) -> Dict[str, float]:
    """Forward FLOPs a step requires under each scope, summed over its layers;
    ``assignments``: per expert layer, as counted (None: the expectation)."""
    out = {
        scope: layers_of(model, kind) * attention_forward_flops(model, batch_size, kind)
        for kind, scope in SCOPES.items()
    }
    layers = len(model["layers"]["layer_types"]) - model["layers"]["num_dense_layers"]
    out["moe"] = layers * counts_hybrid.moe_forward_flops(model, batch_size, assignments)
    out["head"] = counts.head_forward_flops(model, batch_size)
    return out


def step_train_flops(
    model: Mapping[str, Any], batch_size: int, assignments: Optional[float] = None
) -> float:
    """Required FLOPs of one optimizer step (no recomputation counted)."""
    return TRAIN_PASSES * sum(forward_flops_by_scope(model, batch_size, assignments).values())


def attention_train_bytes(model: Mapping[str, Any], batch_size: int, kind: str) -> float:
    """Least HBM traffic of the layers of ``kind`` in a step: per layer the hidden
    states read and their gradient written (compute dtype, 2 bytes), the float32
    projection kernels read and their gradients written. Scores are not in it: a
    fused route need not write them."""
    t, d = counts.positions(model, batch_size), model["embedding_dim"]
    return layers_of(model, kind) * (2.0 * t * d * 2 + 2.0 * attention_weights(model) * 4)


def attention_least_seconds(model, batch_size: int, peaks: Mapping[str, Any], kind: str):
    """(seconds, which bound holds) for the work of the layers of ``kind`` in one step."""
    flops = TRAIN_PASSES * layers_of(model, kind) * attention_forward_flops(model, batch_size, kind)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = attention_train_bytes(model, batch_size, kind) / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")


def attention_roofline_pct(context: Mapping[str, Any], kind: str) -> Optional[float]:
    """What both attention share readers compute: the layers' least seconds over
    the device time per step under their scope. Nothing where the model has no
    such layers or the capture no op under the scope."""
    model, traced = context["model_sizes"], context["traced"]
    device_s = traced["scope_s"].get(SCOPES[kind], 0.0)
    if "sliding_window" not in model.get("attention", {}) or not layers_of(model, kind):
        return None
    if device_s <= 0 or traced["steps"] <= 0:
        return None
    peaks = counts.load_peaks(context["device_kind"])
    per_chip_batch = context["batch_size"] // context["chips"]
    least_s, _ = attention_least_seconds(model, per_chip_batch, peaks, kind)
    return 100.0 * least_s / (device_s / traced["steps"])
