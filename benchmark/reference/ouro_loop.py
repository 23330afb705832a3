"""Plain reference for the looped layer-pattern model (``ouro`` blocks as
Ouro-2.6B publishes them: one stack applied ``total_ut_steps`` times over ONE set
of weights, an exit gate after every step, a loss at every exit).

Straight ``jax.numpy`` in float32 with matmuls at precision ``highest``: forward
pass, the exit-weighted cross-entropy over an UNTIED output table, gradients by
``jax.grad`` and Adam. No kernel, nothing skipped: a head's
[L, L] scores are written out under a materialised causal mask and every exit
scores the whole catalog. It imports nothing of ``replay_tpu`` and takes nothing
the program made: weights come from :func:`init_params` (the seed).

So that one row of 4,096 positions fits beside the weights, gradients and Adam
state, three things are computed in pieces whose intermediates are made again on
the way back (``jax.checkpoint``) instead of kept: every layer application (24 a
step at the published four steps over six layers), attention one head at a time
(scores [B, L, L]), and each exit's head per block of positions (``row_blocks`` of
them; logits [rows, items]). The steps and the exits are ``lax.scan`` / ``lax.map``
loops over one traced body each, so the program a step compiles holds one copy of
the stack and one of the head.

Equations, x [B, L, d], every projection without bias but the gate's, ``rms`` =
RMSNorm (eps 1e-6) with a learned scale (source: the public ``ouro``
configuration, https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json;
the objective: "Scaling Latent Reasoning via Looped Language Models", 2025):

    h0     = table[ids] * keep
    step t = 1..T, u = h_{t-1}:  for each layer l:
             a = u + rms2_l(mha_l(rms1_l(u)));   u = (a + rms4_l(ffn_l(rms3_l(a)))) * keep
             h_t = rms_f(u)      the final norm after EVERY step; h_t is carried on
             g_t = w_g . h_t + b_g   (float32 whatever the precision of the rest)
    mha    : q, k, v = W_q x, W_k x, W_v x -> [H, 128]; q, k <- rotary (theta 1e6,
             half-split pairing); no q/k norm; softmax(q k^T / sqrt(128) + mask) v; W_o
             mask: key j visible from query i iff j <= i and j no padding
    ffn    : W2 (silu(W1 x) * W3 x), width ``ffn_dim``
    lambda = sigmoid(g);  p(t) = lambda_t prod_{j<t} (1 - lambda_j) (t < T),
             p(T) = prod_{j<T} (1 - lambda_j)
    loss   = sum(w * [sum_t p(t) nll(h_t . output_table^T, y) - beta H(p)]) / max(sum(w), 1)
             w = target_mask & valid row, H(p) = -sum_t p(t) log p(t)

Departures from the published description, each marked DEPARTURE at its line:
padding positions are zeroed (item histories are padded; a language model's
sequences are not); positions are indices in the window (a history has no
absolute position; rotary scores depend on differences only). Assumed where the
configuration is silent (``assumed`` in the configuration's file): no q/k norm and
no projection bias, the normed h_t carried into step t + 1, beta 0.1.

``precision="fp8"`` rounds both operands of every matmul but the gate's to float8
(the CONTROL the comparison must fail; the configuration states the gate in
float32). ``fault`` plants what a training cell can get wrong: ``"half_batch"``
(the loss is the mean over the first half of the batch's positions, rows first),
``"loop_3"`` (one step fewer: three for the published four), ``"no_sandwich"`` (rms2, rms4 left out),
``"last_exit_only"`` (the loss at h_T alone: p one-hot at T, no entropy),
``"no_entropy"`` (beta 0), ``"unnormed_carry"`` (u, not h_t, carried into the next
step).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp

from benchmark.reference.lfm2_moe import _rms, _rotary, _swiglu, layer_kinds
from benchmark.reference.transformer_rec import _einsum, adam_update

Params = Dict[str, jnp.ndarray]
FAULTS = (None, "half_batch", "loop_3", "no_sandwich", "last_exit_only", "no_entropy", "unnormed_carry")
BATCH_KEYS = ("item_id", "padding_mask", "labels", "target_mask", "valid")
NORMS = ("mixer_norm", "mixer_post_norm", "ffn_norm", "ffn_post_norm")


def param_shapes(model: Mapping[str, Any]) -> Dict[str, tuple]:
    d, items, f = model["embedding_dim"], model["num_items"], model["ffn_dim"]
    attn = model["attention"]
    width = attn["num_heads"] * attn["head_dim"]
    kv_width = attn["num_kv_heads"] * attn["head_dim"]
    shapes = {
        "item_table": (items + 1, d), "output_table": (items, d), "final_norm.scale": (d,),
        "gate.w": (d, 1), "gate.b": (1,),
    }
    for i, (mixer, sparse) in enumerate(layer_kinds(model)):
        if mixer != "full_attention" or sparse:
            raise ValueError(f"layer {i}: this model has dense full-attention layers only")
        p = f"layers.{i}."
        shapes.update({p + norm + ".scale": (d,) for norm in NORMS})
        shapes.update({
            p + "attn.wq": (d, width), p + "attn.wk": (d, kv_width), p + "attn.wv": (d, kv_width),
            p + "attn.wo": (width, d),
            p + "ffn.w1": (d, f), p + "ffn.w3": (d, f), p + "ffn.w2": (f, d),
        })
    return shapes


def init_params(model: Mapping[str, Any], key) -> Params:
    """Weights from the seed, float32, every leaf random: kernels, the gate and the
    output table at 1/sqrt(fan_in) (so the gate's logit starts near unit
    variance over a normed h_t), norm scales at 1 + 0.02 n, the gate's bias at
    0.1 n, the INPUT table at unit variance (``mellum_moe.init_params``). Call
    under ``jax.jit``."""
    shapes = param_shapes(model)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        noise = jax.random.normal(k, shape, jnp.float32)
        if name.endswith(".scale"):
            out[name] = 1.0 + 0.02 * noise
        elif name == "gate.b":
            out[name] = 0.1 * noise
        elif name == "item_table":
            out[name] = noise
        elif name == "output_table":
            out[name] = noise / math.sqrt(shape[-1])
        else:
            out[name] = noise / math.sqrt(shape[-2])
    return out


def attention_mixer(params, p, x, padding, model, precision):
    attn = model["attention"]
    heads, kv_heads, head_dim = attn["num_heads"], attn["num_kv_heads"], attn["head_dim"]
    batch, length = x.shape[:2]

    def project(w, count):
        return _einsum("ble,ef->blf", x, params[p + w], precision).reshape(batch, length, count, head_dim)

    # DEPARTURE: positions are indices in the window, not absolute token positions
    q = _rotary(project("wq", heads), attn["rope_theta"])
    k = _rotary(project("wk", kv_heads), attn["rope_theta"])
    v = project("wv", kv_heads)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))  # head h reads h // G
    allowed = padding[:, None, :] & jnp.tril(jnp.ones((length, length), bool))[None]
    allowed = allowed | jnp.eye(length, dtype=bool)[None]  # a masked-out (padding) row sees itself
    mask = jnp.where(allowed, 0.0, -jnp.inf)  # [B, L, L]

    @jax.checkpoint  # a head's scores are made again on the way back, not kept
    def one_head(_, head):
        q_head, k_head, v_head = head  # [B, L, D]
        scores = _einsum("bqd,bkd->bqk", q_head, k_head, precision) / math.sqrt(head_dim)
        weights = jax.nn.softmax(scores + mask, axis=-1)
        return None, _einsum("bqk,bkd->bqd", weights, v_head, precision)

    _, mixed = jax.lax.scan(one_head, None, tuple(t.transpose(2, 0, 1, 3) for t in (q, k, v)))
    mixed = mixed.transpose(1, 2, 0, 3).reshape(batch, length, heads * head_dim)
    return _einsum("ble,ef->blf", mixed, params[p + "wo"], precision)


def layer(params, p, u, padding, model, precision, fault=None):
    """One sandwich block: (u + rms2(mha(rms1(u))); then the same around the SwiGLU) * keep."""
    eps = model["norm_eps"]
    post = lambda t, name: t if fault == "no_sandwich" else _rms(t, params[p + name], eps)  # noqa: E731
    mixed = attention_mixer(params, p + "attn.", _rms(u, params[p + "mixer_norm.scale"], eps),
                            padding, model, precision)
    a = u + post(mixed, "mixer_post_norm.scale")
    fed = _swiglu(_rms(a, params[p + "ffn_norm.scale"], eps),
                  *(params[p + "ffn." + w] for w in ("w1", "w3", "w2")), precision)
    # DEPARTURE: padding positions are zero on entry and after every block
    return (a + post(fed, "ffn_post_norm.scale")) * padding[..., None].astype(u.dtype)


def exits(params: Params, batch, model, precision="f32", fault=None):
    """([T, B, L, d] the normed output of every step, [T, B, L] the gate's logits)."""
    eps, padding = model["norm_eps"], batch["padding_mask"]
    steps = model["loop"]["loop_steps"] - (fault == "loop_3")
    # each application is made again from its input on the way back
    apply = [jax.checkpoint(partial(layer, p=f"layers.{i}.", model=model, precision=precision, fault=fault))
             for i in range(len(layer_kinds(model)))]

    def one_step(u, _):  # the same weights every step: one traced body, scanned
        for block in apply:
            u = block(params, u=u, padding=padding)
        h = _rms(u, params["final_norm.scale"], eps)
        gate = _einsum("ble,eo->blo", h, params["gate.w"], "f32")[..., 0] + params["gate.b"][0]
        return (u if fault == "unnormed_carry" else h), (h, gate)

    start = params["item_table"][batch["item_id"]] * padding[..., None].astype(jnp.float32)
    _, (hidden, gates) = jax.lax.scan(one_step, start, None, length=steps)
    return hidden, gates


def exit_probabilities(gate_logits, fault=None):
    """p [T, B, L]: the chance of stopping after each step (module docstring)."""
    stop = jax.nn.sigmoid(gate_logits)
    if fault == "last_exit_only":
        return jnp.zeros_like(stop).at[-1].set(1.0)
    p, left = [], jnp.ones_like(stop[0])
    for t in range(stop.shape[0]):
        if t == stop.shape[0] - 1:
            p.append(left)
        else:
            p.append(stop[t] * left)
            left = left * (1.0 - stop[t])
    return jnp.stack(p)


def position_nll(hidden, table, labels, row_blocks: int, precision):
    """[B, L] nll of the label over the whole catalog, ``row_blocks`` blocks of
    positions at a time."""
    positions = labels.size
    if positions % row_blocks:
        raise ValueError(f"{positions} positions do not divide into {row_blocks} blocks")
    blocks = (hidden.reshape(row_blocks, positions // row_blocks, -1), labels.reshape(row_blocks, -1))

    @jax.checkpoint  # a block's logits are made again on the way back, not kept
    def one_block(_, block):
        rows, label = block
        logits = _einsum("re,ie->ri", rows, table, precision)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, label[:, None], axis=-1)[:, 0]
        return None, nll

    _, nll = jax.lax.scan(one_block, None, blocks)
    return nll.reshape(labels.shape)


def loss_sum(params, batch, weights, model, row_blocks: int, precision="f32", fault=None):
    """(sum over the batch's positions of weight * [expected nll - beta H(p)]: the
    loss's numerator; p [T, B, L], the exit distribution at every position)."""
    hidden, gate_logits = exits(params, batch, model, precision, fault)
    labels = jnp.clip(batch["labels"], 0, model["num_items"] - 1)
    nll = jax.lax.map(lambda h: position_nll(h, params["output_table"], labels, row_blocks, precision), hidden)
    p = exit_probabilities(gate_logits, fault)
    beta = 0.0 if fault in ("no_entropy", "last_exit_only") else model["loop"]["entropy_weight"]
    entropy = jnp.sum(jax.scipy.special.entr(p), axis=0)
    total = jnp.sum(weights * (jnp.sum(p * nll, axis=0) - beta * entropy))
    return total, p


def loss_and_grads(params, batch, model, row_blocks: int, precision="f32", fault=None):
    """The batch's loss, its gradient and p [T, B, L] at the valid targets (0
    elsewhere; the batch's targets, whatever a fault leaves out of the loss)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    weights = valid = (batch["target_mask"] & batch["valid"][:, None]).astype(jnp.float32)
    if fault == "half_batch":
        flat = jnp.arange(weights.size).reshape(weights.shape)
        weights = weights * (flat < weights.size // 2)
    (total, p), grads = jax.value_and_grad(loss_sum, has_aux=True)(
        params, batch, weights, model, row_blocks, precision, fault
    )
    denom = jnp.maximum(jnp.sum(weights), 1.0)
    return total / denom, jax.tree.map(lambda g: g / denom, grads), p * valid


def first_step(params: Params, batch, model, row_blocks: int, precision="f32", fault=None):
    """Step 1 alone, before any update: (loss, gradient, p [T, B, L] at the valid
    targets)."""
    batch = {k: batch[k] for k in BATCH_KEYS}
    loss, grads, p = jax.jit(
        partial(loss_and_grads, model=model, row_blocks=row_blocks, precision=precision, fault=fault)
    )(params, batch)
    return float(loss), grads, p


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

    # the state is updated in place (8.15 GB at the published widths); the
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
