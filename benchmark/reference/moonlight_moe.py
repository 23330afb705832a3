"""Plain reference for the latent-attention layer-pattern model (``deepseek_v3``
blocks as Moonlight publishes them).

Straight ``jax.numpy`` in float32 with matmuls at precision ``highest``: forward
pass, full-catalog cross-entropy over an UNTIED output table, gradients by
``jax.grad`` and Adam. No kernel, no dispatch, nothing skipped: a head's [L, L]
scores are written out under a materialised causal mask, an expert layer is a loop
over the experts held here, each computed for every position, and the shared
expert is computed whole. It imports nothing of ``replay_tpu`` and takes nothing
the program made: weights come from :func:`init_params` (the seed).

So that one row of 4,096 positions fits beside the weights, gradients and Adam
state, three things are computed in pieces whose intermediates are made again on
the way back (``jax.checkpoint``) instead of kept: attention one head at a time
(scores [B, L, L]), the routed experts one after the other, the head per block of
positions (``row_blocks`` of them; logits [rows, items]).

Equations, x [B, L, d], every projection without bias, ``rms`` = RMSNorm (eps
1e-5) with a learned scale (source: the public ``deepseek_v3`` configuration,
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json, with
``q_lora_rank`` null); the first ``num_dense_layers`` layers feed forward densely:

    x      = table[ids] * keep
    h      = x + mla(rms(x));   y = (h + ffn(rms(h))) * keep
    mla    : q = W_q x -> [H, nope + rope], split q_nope | q_rope
             a = W_kva x -> [latent + rope];  c = rms(a[:latent]);  k_rope = a[latent:]
             (ONE rotary key head for all H heads)
             c W_kvb -> [H, nope + value], split k_nope | v
             q_rope, k_rope <- rotary (theta, half-split pairing over the rope dims)
             k = [k_nope | k_rope];  softmax(q k^T / sqrt(nope + rope) + mask) v;  W_o
             mask: key j visible from query i iff j <= i and j no padding
    dense  : W2 (silu(W1 x) * W3 x), width ``ffn_dim``
    sparse : s = sigmoid(W_g x) over all E (float32); sel = top_k(s + b), b a buffer;
             w = s[sel] / (sum s[sel] + 1e-6) * routed_scale
             out = sum_{e in sel, e held} w_e * W2e (silu(W1e x) * W3e x)
                   + Ws2 (silu(Ws1 x) * Ws3 x)          the shared expert, every token
    out    = rms(y_last);  logits = out . output_table^T       (not the input table)
    loss   = sum(nll * w) / max(sum(w), 1),  w = target_mask & valid row

Departures from the published description, each marked DEPARTURE at its line (the
routed layer's at ``lfm2_moe.sparse_ffn``'s, which is reused whole):
padding positions are zeroed (item histories are padded; a language model's
sequences are not); only the routed experts held here contribute (one chip's
share; the rest of the layer lives on other chips) while the shared expert is
whole, as on every chip of the eight; positions are indices in the window (a
history has no absolute position; rotary scores depend on differences only); the
normalisation's epsilon is the program's 1e-6 (the transformers code has 1e-20;
the selected scores sum to ~3, so float32 cannot tell them apart). Assumed where
the configuration is silent (``assumed`` in the configuration's file): the
half-split pairing, no auxiliary loss, no update of the selection bias.

``precision="fp8"`` rounds both operands of every matmul but the router's to
float8 (the CONTROL the comparison must fail; the configuration states the router
in float32). ``fault`` plants what a training cell can get wrong:
``"half_batch"`` (the loss is the mean over the first half of the batch's
positions, rows first), ``"no_experts"`` (the held routed experts' contribution
left out), ``"no_shared"`` (the shared expert left out), ``"no_rope_key"`` (the
rotary key is zero: scores from the no-position part alone), ``"no_latent_norm"``
(the latent goes up un-normalised), ``"scale_128"`` (scores over sqrt(nope), not
sqrt(nope + rope)), ``"no_routed_scale"`` (the routed weights times 1).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp

from benchmark.reference.lfm2_moe import _rms, _rotary, _swiglu, layer_kinds, sparse_ffn
from benchmark.reference.transformer_rec import _einsum, adam_update

Params = Dict[str, jnp.ndarray]
FAULTS = (
    None, "half_batch", "no_experts", "no_shared", "no_rope_key", "no_latent_norm", "scale_128",
    "no_routed_scale",
)
BATCH_KEYS = ("item_id", "padding_mask", "labels", "target_mask", "valid")


def param_shapes(model: Mapping[str, Any]) -> Dict[str, tuple]:
    d, items = model["embedding_dim"], model["num_items"]
    attn, experts = model["latent_attention"], model["experts"]
    heads, latent = attn["num_heads"], attn["kv_latent_dim"]
    nope, rope, value = attn["nope_head_dim"], attn["rope_head_dim"], attn["value_head_dim"]
    held, f = experts["experts_held"], experts["expert_dim"]
    shared = model["shared_experts"]["shared_expert_dim"]
    shapes = {"item_table": (items + 1, d), "output_table": (items, d), "final_norm.scale": (d,)}
    for i, (mixer, sparse) in enumerate(layer_kinds(model)):
        if mixer != "latent_attention":
            raise ValueError(f"layer {i}: this model has latent-attention mixers only")
        p = f"layers.{i}."
        shapes.update({
            p + "mixer_norm.scale": (d,), p + "ffn_norm.scale": (d,),
            p + "attn.wq": (d, heads * (nope + rope)), p + "attn.wkv_a": (d, latent + rope),
            p + "attn.kv_norm.scale": (latent,),
            p + "attn.wkv_b": (latent, heads * (nope + value)), p + "attn.wo": (heads * value, d),
        })
        if sparse:
            shapes.update({
                p + "moe.router": (d, experts["num_experts"]),
                p + "moe.bias": (experts["num_experts"],),
                p + "moe.w1": (held, d, f), p + "moe.w3": (held, d, f), p + "moe.w2": (held, f, d),
                p + "shared.w1": (d, shared), p + "shared.w3": (d, shared),
                p + "shared.w2": (shared, d),
            })
        else:
            f_dense = model["ffn_dim"]
            shapes.update({
                p + "ffn.w1": (d, f_dense), p + "ffn.w3": (d, f_dense), p + "ffn.w2": (f_dense, d),
            })
    return shapes


def init_params(model: Mapping[str, Any], key) -> Params:
    """Weights from the seed, float32, every leaf random: kernels and the output
    table at 1/sqrt(fan_in), norm scales at 1 + 0.02 n, the selection bias at
    0.01 n (``lfm2_moe.init_params``: non-zero, small beside the spread of the top
    scores), the INPUT table at unit variance (``mellum_moe.init_params``: the
    token, not a common direction, leads the stream, so the router starts from
    even loads on every seed). Call under ``jax.jit``."""
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
            out[name] = noise
        elif name == "output_table":
            out[name] = noise / math.sqrt(shape[-1])
        else:
            out[name] = noise / math.sqrt(shape[-2])
    return out


def attention_mixer(params, p, x, padding, model, precision, fault=None):
    attn, eps = model["latent_attention"], model["norm_eps"]
    heads, latent = attn["num_heads"], attn["kv_latent_dim"]
    nope, rope, value = attn["nope_head_dim"], attn["rope_head_dim"], attn["value_head_dim"]
    batch, length = x.shape[:2]
    project = lambda t, w: _einsum("ble,ef->blf", t, params[p + w], precision)  # noqa: E731

    q = project(x, "wq").reshape(batch, length, heads, nope + rope)
    down = project(x, "wkv_a")
    compressed, k_rope = down[..., :latent], down[..., None, latent:]  # one rotary key head
    if fault != "no_latent_norm":
        compressed = _rms(compressed, params[p + "kv_norm.scale"], eps)
    up = project(compressed, "wkv_b").reshape(batch, length, heads, nope + value)
    # DEPARTURE: positions are indices in the window, not absolute token positions
    q_rope = _rotary(q[..., nope:], attn["rope_theta"])
    k_rope = _rotary(k_rope, attn["rope_theta"])
    if fault == "no_rope_key":
        k_rope = jnp.zeros_like(k_rope)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_rope, (batch, length, heads, rope))], axis=-1)
    v = up[..., nope:]
    scale = math.sqrt(nope if fault == "scale_128" else nope + rope)
    allowed = padding[:, None, :] & jnp.tril(jnp.ones((length, length), bool))[None]
    allowed = allowed | jnp.eye(length, dtype=bool)[None]  # a masked-out (padding) row sees itself
    mask = jnp.where(allowed, 0.0, -jnp.inf)  # [B, L, L]

    @jax.checkpoint  # a head's scores are made again on the way back, not kept
    def one_head(_, head):
        q_head, k_head, v_head = head  # [B, L, .]
        scores = _einsum("bqd,bkd->bqk", q_head, k_head, precision) / scale
        weights = jax.nn.softmax(scores + mask, axis=-1)
        return None, _einsum("bqk,bkd->bqd", weights, v_head, precision)

    _, mixed = jax.lax.scan(one_head, None, tuple(t.transpose(2, 0, 1, 3) for t in (q, k, v)))
    mixed = mixed.transpose(1, 2, 0, 3).reshape(batch, length, heads * value)
    return _einsum("ble,ef->blf", mixed, params[p + "wo"], precision)


def routed_ffn(params, p, x, keep, model, precision, fault=None):
    """(the held routed experts' part of the layer's output, assignments per held
    expert [held]): ``lfm2_moe.sparse_ffn``, whose router is this model's too
    (sigmoid scores, selection by score + bias, weights from the scores normalised
    over the selected, times ``routed_scale``) and which marks the DEPARTURE at
    its line: only the routed experts held on this chip contribute. Its
    normalisation's epsilon is the program's 1e-6 (module docstring)."""
    if fault == "no_routed_scale":
        model = {**model, "experts": {**model["experts"], "routed_scale": 1.0}}
    return sparse_ffn(params, p, x, keep, model, precision, fault)


def shared_ffn(params, p, x, precision):
    """The shared expert: one SwiGLU of ``n_shared_experts * moe_intermediate_size``
    that every token passes, whole on every chip."""
    return _swiglu(x, params[p + "w1"], params[p + "w3"], params[p + "w2"], precision)


def sparse_layer(params, p, x, keep, model, precision, fault=None):
    """(routed share + shared expert, the held experts' loads) of layer ``p``."""
    out, load = routed_ffn(params, p + "moe.", x, keep, model, precision, fault)
    if fault != "no_shared":
        out = out + shared_ffn(params, p + "shared.", x, precision)
    return out, load


def hidden_states(params: Params, batch, model, precision="f32", fault=None):
    """([B, L, d] output of the final norm, [expert layers, held] assignments)."""
    eps = model["norm_eps"]
    loads = []
    padding = batch["padding_mask"]
    keep = padding.astype(jnp.float32)
    # DEPARTURE: padding positions are zero on entry and after every block
    x = params["item_table"][batch["item_id"]] * keep[..., None]
    for i, (_, sparse) in enumerate(layer_kinds(model)):
        p = f"layers.{i}."
        h = _rms(x, params[p + "mixer_norm.scale"], eps)
        x = x + attention_mixer(params, p + "attn.", h, padding, model, precision, fault)
        h = _rms(x, params[p + "ffn_norm.scale"], eps)
        if sparse:
            out, load = sparse_layer(params, p, h, keep, model, precision, fault)
            loads.append(load)
        else:
            out = _swiglu(h, *(params[p + "ffn." + w] for w in ("w1", "w3", "w2")), precision)
        x = (x + out) * keep[..., None]
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

    # the state is updated in place (9.1 GB at the published widths); the
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
