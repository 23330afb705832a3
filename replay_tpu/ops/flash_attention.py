"""Fused attention kernel (pallas, TPU). Beyond-parity: the reference has no
custom kernels (torch MultiheadAttention is its hot op, SURVEY.md §2.3); this
is the TPU-first replacement for that path.

The hot op of every sequential recommender here is the [B, H, L, L] attention.
XLA already fuses most of it; this kernel removes the HBM materialization of the
score matrix entirely on the FORWARD pass: each (batch, head) program computes
softmax(QKᵀ/√d + mask) · V inside VMEM with a numerically-stable single pass —
recsys sequence lengths (50-512) fit one VMEM block, so no KV loop is needed
(the ring-attention module handles the sharded long-context regime).

Training works through a ``jax.custom_vjp``: the backward pass recomputes the
attention weights in plain jnp (rematerialization — the standard flash-attention
trade: no stored score matrix on forward, one recompute on backward) and applies
the analytic softmax-attention gradients.

The additive mask stays [B, 1, L, L]; the grid reads the same mask block for
every head via its index map instead of broadcasting to [B, H, L, L] in HBM.

The kernel is compiled by Mosaic on the ``"tpu"`` backend. On ``"cpu"`` (the
test environment) the Pallas interpreter stands in for it and says so once at
WARNING; on any other backend :func:`pallas_interpret` raises — the interpreter
is never chosen silently on an accelerator.
"""

from __future__ import annotations

import logging
from functools import cache, partial

import jax
import jax.numpy as jnp

logger = logging.getLogger("replay_tpu")

# One (batch, head) program holds the [L, L] f32 mask block (double-buffered)
# and the scores in VMEM. AOT compiles for a described v5e (jax 0.9.0 / libtpu
# 0.0.34, bf16): D=64 compiles up to L=1152 and is refused at 1216 ("Scoped
# allocation with size 18.84M and limit 16.00M" at 1280); D=128 compiles at
# 1024 and is refused at 1088. Past this length use the tiled kernel.
MAX_SINGLE_BLOCK_LENGTH = 1024


def _attention_kernel(q_ref, k_ref, v_ref, bias_ref, out_ref):
    """One (batch, head) program: fused masked softmax attention in VMEM."""
    q = q_ref[0, 0].astype(jnp.float32)  # [L, D]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    bias = bias_ref[0, 0]  # [L, L] additive mask (causal+padding), float32
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32)) + bias
    row_max = jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores - row_max)
    denom = jnp.maximum(jnp.sum(probs, axis=-1, keepdims=True), 1e-30)
    out = jnp.dot(probs / denom, v, preferred_element_type=jnp.float32)
    out_ref[0, 0] = out.astype(out_ref.dtype)


def _forward(q, k, v, bias, interpret):
    from jax.experimental import pallas as pl

    batch, heads, length, dim = q.shape
    bias = bias.astype(jnp.float32)
    bias_heads = bias.shape[1]

    block = lambda: pl.BlockSpec((1, 1, length, dim), lambda b, h: (b, h, 0, 0))
    # head-invariant masks ([B, 1, L, L]) are re-read per head, never broadcast
    bias_block = pl.BlockSpec(
        (1, 1, length, length),
        (lambda b, h: (b, h, 0, 0)) if bias_heads > 1 else (lambda b, h: (b, 0, 0, 0)),
    )
    return pl.pallas_call(
        _attention_kernel,
        grid=(batch, heads),
        in_specs=[block(), block(), block(), bias_block],
        out_specs=block(),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, bias)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def flash_attention(
    q: jnp.ndarray,  # [B, H, L, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray,  # [B, 1 or H, L, L] additive mask
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused softmax attention; drop-in for the unfused jnp path, trainable."""
    return _forward(q, k, v, bias, interpret)


def _flash_fwd(q, k, v, bias, interpret):
    return _forward(q, k, v, bias, interpret), (q, k, v, bias)


def _flash_bwd(interpret, residuals, grad_out):
    q, k, v, bias = residuals
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    qf, kf, vf, g = (t.astype(jnp.float32) for t in (q, k, v, grad_out))
    # rematerialize the attention weights (XLA fuses this backward chain)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale + bias.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    grad_v = jnp.einsum("bhqk,bhqd->bhkd", probs, g)
    grad_probs = jnp.einsum("bhqd,bhkd->bhqk", g, vf)
    # softmax backward: dS = P * (dP - sum_k dP * P)
    grad_scores = probs * (grad_probs - jnp.sum(grad_probs * probs, axis=-1, keepdims=True))
    grad_q = jnp.einsum("bhqk,bhkd->bhqd", grad_scores, kf) * scale
    grad_k = jnp.einsum("bhqk,bhqd->bhkd", grad_scores, qf) * scale
    grad_bias = grad_scores
    if bias.shape[1] == 1:  # head-invariant mask: sum the broadcast axis
        grad_bias = jnp.sum(grad_bias, axis=1, keepdims=True)
    return (
        grad_q.astype(q.dtype),
        grad_k.astype(k.dtype),
        grad_v.astype(v.dtype),
        grad_bias.astype(bias.dtype),
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@cache
def _warn_interpreter() -> None:
    logger.warning(
        "pallas kernels run in INTERPRET mode on the cpu backend: results are "
        "valid, timings say nothing about the compiled TPU kernel"
    )


def pallas_interpret() -> bool:
    """Whether pallas kernels must run interpreted on the default backend.

    ``"tpu"`` compiles (False); ``"cpu"`` interprets (True, logged once at
    WARNING); any other backend raises rather than silently interpreting a
    kernel on an accelerator and reporting it as the kernel.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        _warn_interpreter()
        return True
    msg = (
        f"replay_tpu's pallas kernels are written for the TPU backend; the "
        f"default backend is {backend!r}. Use the XLA routes (CE, "
        "use_flash=False), or pass interpret= explicitly to the kernel."
    )
    raise RuntimeError(msg)


def fused_attention_available() -> bool:
    """True when the compiled kernel runs on the default backend (``"tpu"``);
    False when the interpreter stands in (``"cpu"``); raises elsewhere."""
    return not pallas_interpret()
