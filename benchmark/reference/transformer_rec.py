"""Plain reference for the sequential recommenders: SASRec and BERT4Rec.

Straight ``jax.numpy`` in float32 with matmuls at precision ``highest``: forward
pass, full-catalog cross-entropy (computed in blocks of rows so that the
``[rows, items]`` logits fit), gradients by ``jax.grad`` and one Adam update. It
imports nothing of ``replay_tpu`` and takes nothing the program made: weights come
from :func:`init_params` (the seed), dropout masks are drawn here from the keys it
is handed.

One function covers both models; the ``model`` group of a configuration file says
which: ``causal`` (SASRec: causal mask, table scaled by sqrt(d), no input norm) or
not (BERT4Rec: key-padding mask only, ``<MASK>`` vector at hidden tokens, input
norm), the FFN width and its activation.

Equations (pre-LN blocks as published for SASRec; BERT4Rec reuses the block):

    x0   = drop(table[ids]*sqrt(d) + pos)                      (causal)
    x0   = drop(LN(where(visible, table[ids], mask_vec) + pos)) (bidirectional)
    a    = drop(MHA(LN(x)))            x = x + a
    h    = LN(x)                       x = (h + drop(W2 drop(act(W1 h)))) * keep
    out  = LN(x_last)                  logits = out . table[:items]^T
    loss = sum(nll * w) / max(sum(w), 1),  w = target_mask & valid row

``precision`` selects the arithmetic: ``"f32"`` is the reference; ``"fp8"`` rounds
both operands of every matmul to float8 (e4m3, per-tensor scale) and is the
CONTROL that the comparison must fail; ``fault`` plants the faults a training
cell can have (``"half_batch"``: the loss is the mean over the first half of the
rows only).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp

Params = Dict[str, jnp.ndarray]
LN_EPS = 1e-6  # flax.linen.LayerNorm's default, which the models use
ADAM_EPS = 1e-8


def dropout_sites(model: Mapping[str, Any]) -> List[str]:
    """Names of the places a mask is drawn, in forward order."""
    sites = ["embed"]
    for i in range(model["num_blocks"]):
        sites += [f"blocks.{i}.attn", f"blocks.{i}.ffn_inner", f"blocks.{i}.ffn_outer"]
    return sites


def param_shapes(model: Mapping[str, Any]) -> Dict[str, tuple]:
    d, f = model["embedding_dim"], model["ffn_dim"]
    shapes = {
        "item_table": (model["num_items"] + 1, d),  # last row: the padding id
        "pos": (model["max_sequence_length"], d),
    }
    if not model["causal"]:
        shapes.update({"mask_vec": (d,), "in_norm.scale": (d,), "in_norm.bias": (d,)})
    for i in range(model["num_blocks"]):
        p = f"blocks.{i}."
        for norm in ("attn_norm", "ffn_norm"):
            shapes.update({f"{p}{norm}.scale": (d,), f"{p}{norm}.bias": (d,)})
        for w in ("wq", "wk", "wv", "wo"):
            shapes.update({f"{p}{w}": (d, d), f"{p}b{w[1]}": (d,)})
        shapes.update({f"{p}w1": (d, f), f"{p}b1": (f,), f"{p}w2": (f, d), f"{p}b2": (d,)})
    shapes.update({"final_norm.scale": (d,), "final_norm.bias": (d,)})
    return shapes


def init_params(model: Mapping[str, Any], key) -> Params:
    """Weights from the seed, in float32, every leaf random so that no term of
    the forward pass is multiplied by an exact 0 or 1: tables and kernels at
    1/sqrt(fan_in), positions, biases and the mask vector at 0.02, norm scales
    at 1 + 0.02 n. Call it under ``jax.jit`` for one program on the device."""
    shapes = param_shapes(model)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        noise = jax.random.normal(k, shape, jnp.float32)
        if name.endswith(".scale"):
            out[name] = 1.0 + 0.02 * noise
        elif len(shape) == 2 and name != "pos":
            out[name] = noise / math.sqrt(shape[-1] if name == "item_table" else shape[0])
        else:
            out[name] = 0.02 * noise
    return out


def _round_fp8(x):
    """x rounded to float8 e4m3 with a per-tensor scale; gradients pass through."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def _einsum(spec: str, a, b, precision: str):
    if precision == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def _drop(x, key, rate: float):
    if rate == 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def hidden_states(params: Params, batch, keys: Mapping[str, Any], model, precision="f32"):
    """[B, L, d] output of the final norm. ``keys`` maps dropout site -> key."""
    d, heads, rate = model["embedding_dim"], model["num_heads"], model["dropout"]
    ids, padding = batch["item_id"], batch["padding_mask"]
    length = ids.shape[1]
    pos = params["pos"][model["max_sequence_length"] - length :]
    x = params["item_table"][ids]
    if model["causal"]:
        x = x * math.sqrt(d) + pos
    else:
        x = jnp.where(batch["token_mask"][..., None], x, params["mask_vec"]) + pos
        x = _layer_norm(x, params["in_norm.scale"], params["in_norm.bias"])
    x = _drop(x, keys["embed"], rate)

    allowed = jnp.broadcast_to(padding[:, None, :], (ids.shape[0], length, length))
    if model["causal"]:
        allowed = allowed & jnp.tril(jnp.ones((length, length), bool))[None]
    allowed = allowed | jnp.eye(length, dtype=bool)[None]  # a masked-out row sees itself
    bias = jnp.where(allowed, 0.0, -jnp.inf)[:, None]
    keep = padding[..., None].astype(jnp.float32)
    act = {"relu": jax.nn.relu, "gelu": _gelu_tanh}[model["activation"]]

    def heads_of(t):
        return t.reshape(t.shape[0], length, heads, d // heads).transpose(0, 2, 1, 3)

    for i in range(model["num_blocks"]):
        p = f"blocks.{i}."
        h = _layer_norm(x, params[p + "attn_norm.scale"], params[p + "attn_norm.bias"])
        q, k, v = (
            heads_of(_einsum("ble,ef->blf", h, params[p + w], precision) + params[p + b])
            for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv"))
        )
        scores = _einsum("bhqd,bhkd->bhqk", q, k, precision) / math.sqrt(d // heads) + bias
        mixed = _einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v, precision)
        mixed = mixed.transpose(0, 2, 1, 3).reshape(x.shape)
        a = _einsum("ble,ef->blf", mixed, params[p + "wo"], precision) + params[p + "bo"]
        x = x + _drop(a, keys[p + "attn"], rate)
        h = _layer_norm(x, params[p + "ffn_norm.scale"], params[p + "ffn_norm.bias"])
        f = act(_einsum("ble,ef->blf", h, params[p + "w1"], precision) + params[p + "b1"])
        f = _drop(f, keys[p + "ffn_inner"], rate)
        f = _einsum("blf,fe->ble", f, params[p + "w2"], precision) + params[p + "b2"]
        x = (h + _drop(f, keys[p + "ffn_outer"], rate)) * keep
    return _layer_norm(x, params["final_norm.scale"], params["final_norm.bias"])


def head_loss(hidden, table, labels, weights, row_blocks: int, precision="f32"):
    """Full-catalog cross-entropy, the ``[rows, items]`` logits made and dropped
    one block of rows at a time (and made again on the way back)."""
    rows = hidden.shape[0] * hidden.shape[1]
    if rows % row_blocks:
        raise ValueError(f"{rows} rows do not divide into {row_blocks} blocks")
    h = hidden.reshape(row_blocks, rows // row_blocks, hidden.shape[-1])
    y = labels.reshape(row_blocks, -1)
    w = weights.reshape(row_blocks, -1).astype(jnp.float32)

    @jax.checkpoint
    def block(total, xs):
        hb, yb, wb = xs
        logits = _einsum("re,ie->ri", hb, table, precision)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, yb[:, None], axis=-1
        )[:, 0]
        return total + jnp.sum(nll * wb), None

    total, _ = jax.lax.scan(block, jnp.float32(0.0), (h, y, w))
    return total / jnp.maximum(jnp.sum(w), 1.0)


def loss_fn(params, batch, keys, model, row_blocks, precision="f32", fault=None):
    hidden = hidden_states(params, batch, keys, model, precision)
    weights = batch["target_mask"] & batch["valid"][:, None]
    if fault == "half_batch":
        weights = weights & (jnp.arange(weights.shape[0]) < weights.shape[0] // 2)[:, None]
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    num_items = model["num_items"]
    labels = jnp.clip(batch["labels"], 0, num_items - 1)
    return head_loss(
        hidden, params["item_table"][:num_items], labels, weights, row_blocks, precision
    )


def adam_update(params, grads, mu, nu, count, optimizer):
    """optax.adam as published (Kingma & Ba), bias-corrected, eps outside the root."""
    b1, b2, lr = optimizer["b1"], optimizer["b2"], optimizer["learning_rate"]
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1**count, 1 - b2**count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS), params, mu, nu
    )
    return params, mu, nu, count


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
    """Follow ``len(batches)`` optimizer steps from ``params``. Returns the
    per-step losses, the first-moment estimate (the gradients as the optimizer
    holds them) and the parameters after the last step."""

    @jax.jit
    def step(params, mu, nu, count, batch, keys):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, batch, keys, model, row_blocks, precision, fault
        )
        params, mu, nu, count = adam_update(params, grads, mu, nu, count, optimizer)
        return loss, params, mu, nu, count

    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    losses = []
    for batch, keys in zip(batches, step_keys):
        loss, params, mu, nu, count = step(params, mu, nu, count, batch, keys)
        losses.append(loss)
    return [float(x) for x in losses], mu, params
