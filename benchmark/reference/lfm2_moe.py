"""Plain reference for the layer-pattern next-item model (``lfm2_moe`` blocks).

Straight ``jax.numpy`` in float32 with matmuls at precision ``highest``: forward
pass, full-catalog cross-entropy, gradients by ``jax.grad`` and one Adam update.
No kernels and no dispatch: an expert layer is a loop over the experts held here,
each computed for EVERY position and mixed in by its routing weight (zero where
the router did not pick it). It imports nothing of ``replay_tpu`` and takes
nothing the program made: weights come from :func:`init_params` (the seed).

The batch's rows are followed in ``row_blocks`` blocks with summed gradients, so
that the float32 activations of 8 x 1024 positions fit beside the weights,
gradients and Adam state the reference too must hold.

Equations, x [B, L, d], every projection without bias, ``rms`` = RMSNorm with a
learned scale (source: the public ``lfm2_moe`` configuration,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json):

    x      = table[ids] * keep
    h      = x + mixer(rms(x));   y = (h + ffn(rms(h))) * keep       per block
    conv   : [b, c, u] = split(W_in x); v = b*u; conv_t = sum_j k_j * v_{t-j};
             out = W_out (c * conv)
    attn   : q, k <- rms over each head's width; rotary (half-split); causal
             softmax(q k^T / sqrt(head_dim)) v, key/value head h serving query
             heads h*G .. h*G + G-1; W_o
    dense  : W2 (silu(W1 x) * W3 x)
    sparse : s = sigmoid(W_g x); sel = top_k(s + b); w = s[sel] / (sum + 1e-6);
             out = sum_{e in sel, e held} w_e * W2e (silu(W1e x) * W3e x)
    out    = rms(y_last);  logits = out . table[:items]^T
    loss   = sum(nll * w) / max(sum(w), 1),  w = target_mask & valid row

Departures from the published description, each marked DEPARTURE at its line:
padding positions are zeroed (item histories are padded; a language model's
sequences are not), and only the experts held here contribute (one chip's share;
the rest of the layer lives on other chips).

``precision="fp8"`` rounds both operands of every matmul but the router's to
float8 (the CONTROL the comparison must fail; the configuration states the router
in float32). ``fault`` plants what a training cell can get wrong: ``"half_batch"``
(the loss is the mean over the first half of the rows), ``"no_experts"`` (the held
experts' contribution left out), ``"no_bias"`` (selection by ``s``, not ``s + b``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_rec import _einsum, adam_update

Params = Dict[str, jnp.ndarray]
FAULTS = (None, "half_batch", "no_experts", "no_bias")
BATCH_KEYS = ("item_id", "padding_mask", "labels", "target_mask", "valid")


def layer_kinds(model: Mapping[str, Any]):
    """[(mixer kind, feed-forward is sparse)] for every layer, in order."""
    layers = model["layers"]
    return [
        (mixer, i >= layers["num_dense_layers"])
        for i, mixer in enumerate(layers["layer_types"])
    ]


def param_shapes(model: Mapping[str, Any]) -> Dict[str, tuple]:
    d = model["embedding_dim"]
    attn, experts = model["attention"], model["experts"]
    shapes = {"item_table": (model["num_items"] + 1, d), "final_norm.scale": (d,)}
    for i, (mixer, sparse) in enumerate(layer_kinds(model)):
        p = f"layers.{i}."
        shapes.update({p + "mixer_norm.scale": (d,), p + "ffn_norm.scale": (d,)})
        if mixer == "conv":
            shapes.update({
                p + "conv.w_in": (d, 3 * d), p + "conv.kernel": (model["conv"]["kernel"], d),
                p + "conv.w_out": (d, d),
            })
        elif mixer == "full_attention":
            q_width = attn["num_heads"] * attn["head_dim"]
            kv_width = attn["num_kv_heads"] * attn["head_dim"]
            shapes.update({
                p + "attn.wq": (d, q_width), p + "attn.wk": (d, kv_width),
                p + "attn.wv": (d, kv_width), p + "attn.wo": (q_width, d),
                p + "attn.q_norm.scale": (attn["head_dim"],),
                p + "attn.k_norm.scale": (attn["head_dim"],),
            })
        else:
            raise ValueError(f"unknown layer type {mixer!r}")
        if sparse:
            held, f = experts["experts_held"], experts["expert_dim"]
            shapes.update({
                p + "moe.router": (d, experts["num_experts"]),
                p + "moe.bias": (experts["num_experts"],),
                p + "moe.w1": (held, d, f), p + "moe.w3": (held, d, f), p + "moe.w2": (held, f, d),
            })
        else:
            f = model["ffn_dim"]
            shapes.update({p + "ffn.w1": (d, f), p + "ffn.w3": (d, f), p + "ffn.w2": (f, d)})
    return shapes


def init_params(model: Mapping[str, Any], key) -> Params:
    """Weights from the seed, float32, every leaf random: kernels and the item
    table at 1/sqrt(fan_in), norm scales at 1 + 0.02 n, the expert bias at 0.01 n:
    non-zero, so that a selection that forgets it differs (for three tokens in ten
    at the published widths), and small beside the spread of the top scores (the
    sigmoid is flat up there: at 0.1 n the bias alone picks the experts and one
    held expert gets 8x the mean). Call under ``jax.jit``."""
    shapes = param_shapes(model)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        noise = jax.random.normal(k, shape, jnp.float32)
        if name.endswith(".scale"):
            out[name] = 1.0 + 0.02 * noise
        elif name.endswith("moe.bias"):
            out[name] = 0.01 * noise
        elif name == "item_table":
            out[name] = noise / math.sqrt(shape[-1])
        elif name.endswith("conv.kernel"):
            out[name] = noise / math.sqrt(shape[0])
        else:
            out[name] = noise / math.sqrt(shape[-2])
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x [B, L, H, D]; position = index in the window (scores depend on
    differences only). Half-split pairing (x[i], x[i + D/2]), the transformers
    implementation's; the configuration gives theta and no pairing."""
    length, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq  # [L, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def conv_mixer(params, p, x, precision):
    gates = _einsum("ble,ef->blf", x, params[p + "w_in"], precision)
    b, c, u = jnp.split(gates, 3, axis=-1)
    v, kernel = b * u, params[p + "kernel"]
    conv = v * kernel[0]
    for j in range(1, kernel.shape[0]):  # depth-wise, causal: v_{t-j}, zeros before the window
        conv = conv + jnp.pad(v, ((0, 0), (j, 0), (0, 0)))[:, : v.shape[1]] * kernel[j]
    return _einsum("ble,ef->blf", c * conv, params[p + "w_out"], precision)


def attention_mixer(params, p, x, padding, model, precision):
    attn, eps = model["attention"], model["norm_eps"]
    heads, kv_heads, head_dim = attn["num_heads"], attn["num_kv_heads"], attn["head_dim"]
    batch, length = x.shape[:2]

    def project(w, count):
        return _einsum("ble,ef->blf", x, params[p + w], precision).reshape(
            batch, length, count, head_dim
        )

    q = _rotary(_rms(project("wq", heads), params[p + "q_norm.scale"], eps), attn["rope_theta"])
    k = _rotary(_rms(project("wk", kv_heads), params[p + "k_norm.scale"], eps), attn["rope_theta"])
    v = project("wv", kv_heads)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))  # head h reads h // G
    allowed = padding[:, None, :] & jnp.tril(jnp.ones((length, length), bool))[None]
    allowed = allowed | jnp.eye(length, dtype=bool)[None]  # a masked-out row sees itself
    scores = _einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(head_dim)
    weights = jax.nn.softmax(scores + jnp.where(allowed, 0.0, -jnp.inf)[:, None], axis=-1)
    mixed = _einsum("bhqk,bkhd->bqhd", weights, v, precision).reshape(batch, length, -1)
    return _einsum("ble,ef->blf", mixed, params[p + "wo"], precision)


def _swiglu(x, w1, w3, w2, precision):
    inner = jax.nn.silu(_einsum("ble,ef->blf", x, w1, precision)) * _einsum(
        "ble,ef->blf", x, w3, precision
    )
    return _einsum("blf,fe->ble", inner, w2, precision)


def routing(params, p, x, model, fault=None):
    """(selected experts [B, L, k], their weights [B, L, k]); float32 whatever
    the precision of the rest: the configuration states the router in float32."""
    experts = model["experts"]
    scores = jax.nn.sigmoid(_einsum("ble,ef->blf", x, params[p + "router"], "f32"))
    bias = 0.0 if fault == "no_bias" else jax.lax.stop_gradient(params[p + "bias"])
    _, selected = jax.lax.top_k(scores + bias, experts["experts_per_token"])
    weights = jnp.take_along_axis(scores, selected, axis=-1)  # from s, not s + b
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return selected, weights * experts["routed_scale"]


def sparse_ffn(params, p, x, keep, model, precision, fault=None):
    """(the held experts' part of the layer's output, assignments per held expert
    [held] over the positions that are not padding)."""
    experts = model["experts"]
    selected, weights = routing(params, p, x, model, fault)
    here = experts["expert_offset"] + jnp.arange(experts["experts_held"])
    picked = (selected[..., None] == here) & (keep[..., None, None] > 0)  # [B, L, k, held]
    load = jnp.sum(picked, axis=(0, 1, 2), dtype=jnp.int32)
    out = jnp.zeros_like(x)
    if fault == "no_experts":
        return out, load
    # DEPARTURE: only the experts held on this chip contribute (guide: the chip's
    # share); the published layer sums over all of its experts
    @jax.checkpoint  # an expert's intermediates are made again on the way back, not kept
    def one_expert(out, expert):
        index, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(selected == index, weights, 0.0), axis=-1)  # 0 where not picked
        return out + _swiglu(x, w1, w3, w2, precision) * (weight * keep)[..., None], None

    out, _ = jax.lax.scan(  # one expert after the other: a loop, rolled so that it compiles once
        one_expert, out, (here, params[p + "w1"], params[p + "w3"], params[p + "w2"])
    )
    return out, load


def hidden_states(params: Params, batch, model, precision="f32", fault=None):
    """([B, L, d] output of the final norm, [expert layers, held] assignments)."""
    eps = model["norm_eps"]
    loads = []
    padding = batch["padding_mask"]
    keep = padding.astype(jnp.float32)
    # DEPARTURE: padding positions are zero on entry and after every block
    x = params["item_table"][batch["item_id"]] * keep[..., None]
    for i, (mixer, sparse) in enumerate(layer_kinds(model)):
        p = f"layers.{i}."
        h = _rms(x, params[p + "mixer_norm.scale"], eps)
        if mixer == "conv":
            x = x + conv_mixer(params, p + "conv.", h, precision)
        else:
            x = x + attention_mixer(params, p + "attn.", h, padding, model, precision)
        h = _rms(x, params[p + "ffn_norm.scale"], eps)
        if sparse:
            out, load = sparse_ffn(params, p + "moe.", h, keep, model, precision, fault)
            x = x + out
            loads.append(load)
        else:
            w = {k: params[p + "ffn." + k] for k in ("w1", "w3", "w2")}
            x = x + _swiglu(h, w["w1"], w["w3"], w["w2"], precision)
        x = x * keep[..., None]
    return _rms(x, params["final_norm.scale"], eps), jnp.stack(loads)


def block_loss_sum(params, batch, weights, model, precision="f32", fault=None):
    """(sum over the block's rows of nll * weight: the loss's numerator; the
    block's expert loads)."""
    hidden, loads = hidden_states(params, batch, model, precision, fault)
    num_items = model["num_items"]
    logits = _einsum("ble,ie->bli", hidden, params["item_table"][:num_items], precision)
    labels = jnp.clip(batch["labels"], 0, num_items - 1)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    )[..., 0]
    return jnp.sum(nll * weights), loads


def loss_and_grads(params, batch, model, row_blocks: int, precision="f32", fault=None):
    """The batch's loss, its gradient and its expert loads, the rows followed
    ``row_blocks`` at a time: numerators, their gradients and the loads summed, the
    first two divided once by the weight."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    weights = (batch["target_mask"] & batch["valid"][:, None]).astype(jnp.float32)
    rows = weights.shape[0]
    if fault == "half_batch":
        weights = weights * (jnp.arange(rows) < rows // 2)[:, None]
    if rows % row_blocks:
        raise ValueError(f"{rows} rows do not divide into {row_blocks} blocks")
    names = ("item_id", "padding_mask", "labels")
    blocks = {k: batch[k].reshape(row_blocks, rows // row_blocks, -1) for k in names}
    blocks["weights"] = weights.reshape(row_blocks, rows // row_blocks, -1)

    def one_block(carry, block):
        total, grads, loads = carry
        (value, load), grad = jax.value_and_grad(block_loss_sum, has_aux=True)(
            params, block, block["weights"], model, precision, fault
        )
        return (total + value, jax.tree.map(jnp.add, grads, grad), loads + load), None

    sparse_layers = sum(sparse for _, sparse in layer_kinds(model))
    zero = (
        jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params),
        jnp.zeros((sparse_layers, model["experts"]["experts_held"]), jnp.int32),
    )
    (total, grads, loads), _ = jax.lax.scan(one_block, zero, blocks)
    denom = jnp.maximum(jnp.sum(weights), 1.0)
    return total / denom, jax.tree.map(lambda g: g / denom, grads), loads


def first_step(params: Params, batch, model, row_blocks: int, precision="f32", fault=None):
    """Step 1 alone, before any update: (loss, gradient, expert loads). What the
    comparison reads apart from the trajectory: at step 1 program and reference
    hold the same weights, so nothing but the arithmetic separates them."""
    batch = {k: batch[k] for k in BATCH_KEYS}
    loss, grads, loads = jax.jit(
        partial(loss_and_grads, model=model, row_blocks=row_blocks, precision=precision, fault=fault)
    )(params, batch)
    return float(loss), grads, loads


def train_steps(
    params: Params,
    batches: Sequence[Mapping[str, Any]],
    step_keys: Sequence[Mapping[str, Any]],
    model: Mapping[str, Any],
    optimizer: Mapping[str, Any],
    row_blocks: int,
    precision: str = "f32",
    fault=None,
):
    """Follow ``len(batches)`` optimizer steps from ``params``: per-step losses,
    Adam's first moment after them, the parameters after the last step.
    ``step_keys`` is the interface's (the model has no dropout: it is not read)."""
    del step_keys

    # the state is updated in place (7.5 GB at the published widths); the
    # caller's weights are read again after the last step, so they are copied
    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, count, batch):
        loss, grads, _ = loss_and_grads(params, batch, model, row_blocks, precision, fault)
        params, mu, nu, count = adam_update(params, grads, mu, nu, count, optimizer)
        return loss, params, mu, nu, count

    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    params = jax.tree.map(jnp.copy, params)
    count = jnp.zeros((), jnp.float32)
    losses = []
    for batch in batches:
        batch = {k: batch[k] for k in BATCH_KEYS}
        loss, params, mu, nu, count = step(params, mu, nu, count, batch)
        losses.append(loss)
    return [float(x) for x in losses], mu, params
