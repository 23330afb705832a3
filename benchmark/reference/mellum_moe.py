"""Plain reference for the window-and-full layer-pattern model (``mellum`` blocks).

Straight ``jax.numpy`` in float32 with matmuls at precision ``highest``: forward
pass, full-catalog cross-entropy over an UNTIED output table, gradients by
``jax.grad`` and Adam. No kernel, no dispatch, nothing skipped: a sliding layer is
full attention under a MATERIALISED band mask, an expert layer a loop over the
experts held here, each computed for every position. It imports nothing of
``replay_tpu`` and takes nothing the program made: weights come from
:func:`init_params` (the seed).

So that one row of 8,192 positions fits beside the weights, gradients and Adam
state, three things are computed in blocks whose intermediates are made again on
the way back (``jax.checkpoint``) instead of kept: attention per key/value head
and per ``QUERY_ROWS`` query rows (scores [G, rows, L]), the experts one after the
other, the head per block of positions (``row_blocks`` of them; logits [rows, items]).

Equations, x [B, L, d], every projection without bias, ``rms`` = RMSNorm (eps
1e-6) with a learned scale (source: the public ``mellum`` configuration,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json);
layer i is of kind ``layer_types[i]``, every layer sparse:

    x      = table[ids] * keep
    h      = x + attn_kind(rms(x));   y = (h + moe(rms(h))) * keep
    attn   : q = W_q x (H heads), k, v = W_k x, W_v x (Hkv heads); q, k <- rms over
             each head's width; q, k <- rotary_kind (half-split pairing), cos and
             sin times attention_factor_kind;
             softmax(q k^T / sqrt(head_dim) + mask_kind) v; W_o
             query head h reads key/value head h // (H / Hkv)
             mask: key j visible from query i iff j <= i, j no padding, and in a
             sliding layer i - j < sliding_window
    rotary : sliding_attention  inv_freq_i = theta^(-2i/D), factor 1
             full_attention     YaRN: s = factor, L0 = original_max_position_embeddings,
               dim(r) = D ln(L0 / (2 pi r)) / (2 ln theta); low = floor(dim(beta_fast)),
               high = ceil(dim(beta_slow)), clipped to [0, D - 1];
               ramp_i = clip((i - low) / (high - low), 0, 1);
               inv_freq_i = theta^(-2i/D) * ((1 - ramp_i) + ramp_i / s);
               attention_factor as published (0.1 ln s + 1)
    moe    : p = softmax(W_g x) over all E (float32); sel = top_k(p);
             w = p[sel] / sum p[sel];
             out = sum_{e in sel, e held} w_e * W2e (silu(W1e x) * W3e x)
    out    = rms(y_last);  logits = out . output_table^T       (not the input table)
    loss   = sum(nll * w) / max(sum(w), 1),  w = target_mask & valid row

Departures from the published description, each marked DEPARTURE at its line:
padding positions are zeroed (item histories are padded; a language model's
sequences are not); only the experts held here contribute (one chip's share; the
rest of the layer lives on other chips); positions are indices in the window (a
history has no absolute position; rotary scores depend on differences only).
Assumed where the configuration is silent (``assumed`` in the configuration's
file): the RMS norm on q and k, the half-split pairing, no auxiliary loss, no
multi-token-prediction head.

``precision="fp8"`` rounds both operands of every matmul but the router's to
float8 (the CONTROL the comparison must fail; the configuration states the router
in float32). ``fault`` plants what a training cell can get wrong: ``"half_batch"``
(the loss is the mean over the first half of the batch's positions, rows first),
``"no_experts"`` (the held experts' contribution left out), ``"no_window"`` (the
sliding layers see every earlier key), ``"no_yarn"`` (the full layers turn by the
one-theta frequencies, factor 1), ``"no_renorm"`` (the selected experts' weights
are not renormalised).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.lfm2_moe import _rms, _swiglu, layer_kinds
from benchmark.reference.transformer_rec import _einsum, adam_update

Params = Dict[str, jnp.ndarray]
FAULTS = (None, "half_batch", "no_experts", "no_window", "no_yarn", "no_renorm")
BATCH_KEYS = ("item_id", "padding_mask", "labels", "target_mask", "valid")
QUERY_ROWS = 1024  # query rows of one attention block (scores [G, rows, L] float32)


def param_shapes(model: Mapping[str, Any]) -> Dict[str, tuple]:
    d, items = model["embedding_dim"], model["num_items"]
    attn, experts = model["attention"], model["experts"]
    q_width, kv_width = (attn[k] * attn["head_dim"] for k in ("num_heads", "num_kv_heads"))
    held, f = experts["experts_held"], experts["expert_dim"]
    shapes = {"item_table": (items + 1, d), "output_table": (items, d), "final_norm.scale": (d,)}
    for i, (mixer, sparse) in enumerate(layer_kinds(model)):
        if mixer not in ("sliding_attention", "full_attention") or not sparse:
            raise ValueError(f"layer {i}: this model has attention mixers and sparse layers only")
        p = f"layers.{i}."
        shapes.update({
            p + "mixer_norm.scale": (d,), p + "ffn_norm.scale": (d,),
            p + "attn.wq": (d, q_width), p + "attn.wk": (d, kv_width),
            p + "attn.wv": (d, kv_width), p + "attn.wo": (q_width, d),
            p + "attn.q_norm.scale": (attn["head_dim"],),
            p + "attn.k_norm.scale": (attn["head_dim"],),
            p + "moe.router": (d, experts["num_experts"]),
            p + "moe.w1": (held, d, f), p + "moe.w3": (held, d, f), p + "moe.w2": (held, f, d),
        })
    return shapes


def init_params(model: Mapping[str, Any], key) -> Params:
    """Weights from the seed, float32, every leaf random: kernels and the output
    table at 1/sqrt(fan_in), norm scales at 1 + 0.02 n, the INPUT table at unit
    variance (``torch.nn.Embedding``'s default; the head is a table of its own, so
    nothing ties the input rows to the logits' scale). At 1/sqrt(d) the rows have
    norm 1 and the first attention layer's output, a common direction of norm ~2,
    drowns them: every token then asks the router for the same experts, a layer's
    held load starts anywhere between 0 and 30,000 of 65,536 and the step time
    follows it from seed to seed (PERF.md, Findings PR 31). Unit variance keeps the
    token in the stream: 8,192 +- 10% assignments a layer at step 1 on every seed.
    Call under ``jax.jit``."""
    shapes = param_shapes(model)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        noise = jax.random.normal(k, shape, jnp.float32)
        if name.endswith(".scale"):
            out[name] = 1.0 + 0.02 * noise
        elif name == "item_table":
            out[name] = noise
        elif name == "output_table":
            out[name] = noise / math.sqrt(shape[-1])
        else:
            out[name] = noise / math.sqrt(shape[-2])
    return out


def rotary_frequencies(attn: Mapping[str, Any], kind: str, fault=None):
    """(inv_freq [D/2] float32, attention factor) of one layer type, from its
    ``rope_parameters`` (float64 until the last line)."""
    rope, dim = attn["rope_parameters"][kind], attn["head_dim"]
    pairs = np.arange(dim // 2, dtype=np.float64)
    inv_freq = float(rope["rope_theta"]) ** (-2.0 * pairs / dim)
    if rope["rope_type"] == "default" or fault == "no_yarn":
        return jnp.asarray(inv_freq, jnp.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    turns = lambda r: dim * math.log(rope["original_max_position_embeddings"] / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(rope["rope_theta"])
    )
    low = max(math.floor(turns(rope["beta_fast"])), 0)
    high = min(math.ceil(turns(rope["beta_slow"])), dim - 1)
    ramp = np.clip((pairs - low) / (high - low), 0.0, 1.0)
    inv_freq = inv_freq * ((1.0 - ramp) + ramp / rope["factor"])
    return jnp.asarray(inv_freq, jnp.float32), float(rope["attention_factor"])


def _rotary(x, inv_freq, factor):
    """x [B, L, H, D]; position = index in the window. Half-split pairing
    (x[i], x[i + D/2]), the transformers implementation's."""
    # DEPARTURE: positions are indices in the window, not absolute token positions
    half = x.shape[-1] // 2
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq  # [L, D/2]
    cos, sin = (f(angles)[:, None, :] * factor for f in (jnp.cos, jnp.sin))
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def attention_mixer(params, p, x, padding, model, kind, precision, fault=None):
    attn, eps = model["attention"], model["norm_eps"]
    heads, kv_heads, head_dim = attn["num_heads"], attn["num_kv_heads"], attn["head_dim"]
    batch, length = x.shape[:2]
    group = heads // kv_heads
    window = attn["sliding_window"] if kind == "sliding_attention" and fault != "no_window" else None
    inv_freq, factor = rotary_frequencies(attn, kind, fault)

    def project(w, count):
        return _einsum("ble,ef->blf", x, params[p + w], precision).reshape(
            batch, length, count, head_dim
        )

    q = _rotary(_rms(project("wq", heads), params[p + "q_norm.scale"], eps), inv_freq, factor)
    k = _rotary(_rms(project("wk", kv_heads), params[p + "k_norm.scale"], eps), inv_freq, factor)
    v = project("wv", kv_heads)
    rows = QUERY_ROWS if length % QUERY_ROWS == 0 else length
    # [kv heads, query blocks, B, rows, G, D]: query head h * G + g reads key/value head h
    q = q.reshape(batch, length // rows, rows, kv_heads, group, head_dim).transpose(3, 1, 0, 2, 4, 5)
    k, v = (t.transpose(2, 0, 1, 3) for t in (k, v))  # [kv heads, B, L, D]
    keys = jnp.arange(length)

    @jax.checkpoint  # a block's scores are made again on the way back, not kept
    def one_block(q_block, start, k_head, v_head):
        distance = (start + jnp.arange(rows))[:, None] - keys[None, :]  # i - j, [rows, L]
        allowed = (distance >= 0) & padding[:, None, :]
        if window is not None:
            allowed = allowed & (distance < window)
        allowed = allowed | (distance == 0)  # a masked-out (padding) row sees itself
        scores = _einsum("bqgd,bkd->bgqk", q_block, k_head, precision) / math.sqrt(head_dim)
        weights = jax.nn.softmax(scores + jnp.where(allowed, 0.0, -jnp.inf)[:, None], axis=-1)
        return _einsum("bgqk,bkd->bqgd", weights, v_head, precision)

    def one_head(_, head):
        q_head, k_head, v_head = head
        starts = jnp.arange(length // rows) * rows
        _, out = jax.lax.scan(
            lambda _, block: (None, one_block(block[0], block[1], k_head, v_head)),
            None, (q_head, starts),
        )
        return None, out  # [query blocks, B, rows, G, D]

    _, mixed = jax.lax.scan(one_head, None, (q, k, v))  # [kv heads, blocks, B, rows, G, D]
    mixed = mixed.transpose(2, 1, 3, 0, 4, 5).reshape(batch, length, heads * head_dim)
    return _einsum("ble,ef->blf", mixed, params[p + "wo"], precision)


def routing(params, p, x, model, fault=None):
    """(selected experts [B, L, k], their weights [B, L, k]); float32 whatever
    the precision of the rest: the configuration states the router in float32."""
    logits = _einsum("ble,ef->blf", x, params[p + "router"], "f32")
    weights, selected = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), model["experts"]["experts_per_token"])
    if fault != "no_renorm":
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return selected, weights


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

    out, _ = jax.lax.scan(
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
    for i, (kind, _) in enumerate(layer_kinds(model)):
        p = f"layers.{i}."
        h = _rms(x, params[p + "mixer_norm.scale"], eps)
        x = x + attention_mixer(params, p + "attn.", h, padding, model, kind, precision, fault)
        h = _rms(x, params[p + "ffn_norm.scale"], eps)
        out, load = sparse_ffn(params, p + "moe.", h, keep, model, precision, fault)
        x = (x + out) * keep[..., None]
        loads.append(load)
    return _rms(x, params["final_norm.scale"], eps), jnp.stack(loads)


def loss_sum(params, batch, weights, model, row_blocks: int, precision="f32", fault=None):
    """(sum over the batch's positions of nll * weight: the loss's numerator; the
    expert loads). The head follows the positions ``row_blocks`` blocks at a time."""
    hidden, loads = hidden_states(params, batch, model, precision, fault)
    num_items = model["num_items"]
    labels = jnp.clip(batch["labels"], 0, num_items - 1)
    positions = weights.size
    if positions % row_blocks:
        raise ValueError(f"{positions} positions do not divide into {row_blocks} blocks")
    blocks = (
        hidden.reshape(row_blocks, positions // row_blocks, -1),
        labels.reshape(row_blocks, -1), weights.reshape(row_blocks, -1),
    )

    @jax.checkpoint  # a block's logits are made again on the way back, not kept
    def one_block(total, block):
        rows, label, weight = block
        logits = _einsum("re,ie->ri", rows, params["output_table"], precision)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, label[:, None], axis=-1
        )[:, 0]
        return total + jnp.sum(nll * weight), None

    total, _ = jax.lax.scan(one_block, jnp.float32(0.0), blocks)
    return total, loads


def loss_and_grads(params, batch, model, row_blocks: int, precision="f32", fault=None):
    """The batch's loss, its gradient and its expert loads."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    weights = (batch["target_mask"] & batch["valid"][:, None]).astype(jnp.float32)
    if fault == "half_batch":
        flat = jnp.arange(weights.size).reshape(weights.shape)
        weights = weights * (flat < weights.size // 2)
    (total, loads), grads = jax.value_and_grad(loss_sum, has_aux=True)(
        params, batch, weights, model, row_blocks, precision, fault
    )
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

    # the state is updated in place (5.4 GB at the published widths); the
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
