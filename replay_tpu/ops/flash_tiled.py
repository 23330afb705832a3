"""Tiled flash attention (pallas, TPU): the LONG-sequence single-chip kernel.

The single-block kernel (ops/flash_attention.py) holds the whole [L, L] score
matrix of one (batch, head) in VMEM — past L≈1024 that exceeds the ~16 MB VMEM
budget (ops.flash_attention.MAX_SINGLE_BLOCK_LENGTH). This kernel implements the standard flash
recipe instead: grid ``(B, H, q_blocks, kv_blocks)`` with the kv axis innermost
(sequential on TPU), carrying the online-softmax state (running max, running
sum, output accumulator) in VMEM scratch across kv steps. VMEM peak is
O(block_q · block_k + block·D), independent of L, and nothing O(L²) ever
exists — not even the mask, which is computed in-kernel from block indices
(causal) plus a per-KEY additive bias row ([B, L], typically 0 / -1e30 from a
padding mask) instead of the [B, 1, L, L] bias tensor of the short-L kernel.

Training: ``jax.custom_vjp`` with the memory-efficient blockwise backward —
a ``lax.scan`` over kv blocks recomputing each block's probabilities from the
saved logsumexp (O(B·H·L·block_k) peak, never O(L²)).

Beyond-parity: the reference has no custom kernels; its torch path
materializes [B, H, L, L] (SURVEY.md §2.3). The mesh-sharded regime is ring
attention (replay_tpu/parallel/ring.py); this kernel is the within-chip story.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref, m_ref, l_ref, acc_ref,
            *, block_q, block_k, num_k, causal):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [bk, D]
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [bq, bk]
        scores = scores + bias_ref[0]  # [1, bk] per-key bias (padding)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where(cols <= rows, scores, NEG_INF)

        m_prev = m_ref[:, 0][:, None]  # [bq, 1]
        l_prev = l_ref[:, 0][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        # fully-masked rows keep m == NEG_INF; exp(NEG_INF - NEG_INF) would be
        # 1, so mask the probabilities explicitly
        probs = jnp.exp(scores - m_new)
        probs = jnp.where(scores <= NEG_INF / 2, 0.0, probs)
        correction = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * correction + jnp.sum(probs, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jnp.dot(
            probs, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # kv blocks entirely ABOVE the diagonal contribute nothing: skip both
        # matmuls (≈2× less causal work); init/finalize still run every step
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == num_k - 1)
    def _finalize():
        l_final = l_ref[:, 0][:, None]
        m_final = m_ref[:, 0][:, None]
        denom = jnp.maximum(l_final, 1e-30)
        out_ref[0, 0] = (acc_ref[...] / denom).astype(out_ref.dtype)
        # logsumexp residual for the blockwise backward; NEG_INF on dead rows
        lse = jnp.where(m_final <= NEG_INF / 2, NEG_INF, m_final + jnp.log(denom))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _pad_to(x, axis, multiple, value=0.0):
    length = x.shape[axis]
    pad = (-length) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _forward(q, k, v, kv_bias, causal, block_q, block_k, interpret):
    batch, heads, length, dim = q.shape
    block_q = min(block_q, max(length, 1))
    block_k = min(block_k, max(length, 1))
    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    # the bias rides as [B, 1, Lk]: Mosaic wants a block's last two dims to be
    # (8, 128)-aligned or the whole array's, and a (1, block_k) block of a
    # [B, Lk] array is neither once B > 1
    bias = _pad_to(kv_bias.astype(jnp.float32), 1, block_k, value=NEG_INF)[:, None, :]
    lq, lk = qp.shape[2], kp.shape[2]
    num_q, num_k = lq // block_q, lk // block_k

    grid = (batch, heads, num_q, num_k)
    qspec = pl.BlockSpec((1, 1, block_q, dim), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block_k, dim), lambda b, h, i, j: (b, h, j, 0))
    bspec = pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j))
    out_spec = pl.BlockSpec((1, 1, block_q, dim), lambda b, h, i, j: (b, h, i, 0))
    lse_spec = pl.BlockSpec((1, 1, block_q, 128), lambda b, h, i, j: (b, h, i, 0))

    from jax.experimental.pallas import tpu as pltpu

    scratch = [
        pltpu.VMEM((block_q, 128), jnp.float32),  # running max
        pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
        pltpu.VMEM((block_q, dim), jnp.float32),  # output accumulator
    ]
    out, lse = pl.pallas_call(
        partial(_kernel, block_q=block_q, block_k=block_k, num_k=num_k, causal=causal),
        grid=grid,
        in_specs=[qspec, kspec, kspec, bspec],
        out_specs=[out_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, heads, lq, 128), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(qp, kp, vp, bias)
    return out[:, :, :length], lse[:, :, :length, 0]


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_tiled(
    q: jnp.ndarray,  # [B, H, L, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_bias: jnp.ndarray,  # [B, L] additive per-key bias (0 valid / -1e30 pad)
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Length-tiled fused attention; VMEM and HBM stay O(L·block), not O(L²)."""
    out, _ = _forward(q, k, v, kv_bias, causal, block_q, block_k, interpret)
    return out


def padding_mask_bias(padding_mask: jnp.ndarray) -> jnp.ndarray:
    """[B, L] bool (True = real token) → the additive per-key bias row."""
    return jnp.where(padding_mask, 0.0, NEG_INF).astype(jnp.float32)


def _fwd(q, k, v, kv_bias, causal, block_q, block_k, interpret):
    out, lse = _forward(q, k, v, kv_bias, causal, block_q, block_k, interpret)
    return out, (q, k, v, kv_bias, out, lse)


def _bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, kv_bias, out, lse = residuals
    del block_q, interpret
    batch, heads, length, dim = q.shape
    qf, kf, vf, gf = (t.astype(jnp.float32) for t in (q, k, v, g))
    scale = 1.0 / jnp.sqrt(jnp.asarray(dim, jnp.float32))
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # [B, H, L]
    rows = jnp.arange(length)

    block = min(block_k, max(length, 1))
    pad = (-length) % block
    kp = _pad_to(kf, 2, block)
    vp = _pad_to(vf, 2, block)
    bias_p = _pad_to(kv_bias.astype(jnp.float32), 1, block, value=NEG_INF)
    num_k = kp.shape[2] // block
    # scan axis (kv block) must LEAD; keep [B, H, bk, D] intact behind it
    k_blocks = jnp.moveaxis(kp.reshape(batch, heads, num_k, block, dim), 2, 0)
    v_blocks = jnp.moveaxis(vp.reshape(batch, heads, num_k, block, dim), 2, 0)
    bias_blocks = bias_p.reshape(batch, num_k, block).swapaxes(0, 1)

    def step(dq_acc, inputs):
        j, kj, vj, bj = inputs  # kj/vj [B, H, bk, D], bj [B, bk]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj) * scale + bj[:, None, None, :]
        if causal:
            cols = j * block + jnp.arange(block)
            s = jnp.where(cols[None, None, None, :] <= rows[None, None, :, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vj)
        ds = p * (dp - delta[..., None])
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
        dbias_j = jnp.sum(ds, axis=(1, 2))  # [B, bk]
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kj) * scale
        return dq_acc, (dk_j, dv_j, dbias_j)

    dq, (dk_b, dv_b, dbias_b) = jax.lax.scan(
        step,
        jnp.zeros_like(qf),
        (jnp.arange(num_k), k_blocks, v_blocks, bias_blocks),
    )
    dk = jnp.moveaxis(dk_b, 0, 2).reshape(batch, heads, num_k * block, dim)[:, :, :length]
    dv = jnp.moveaxis(dv_b, 0, 2).reshape(batch, heads, num_k * block, dim)[:, :, :length]
    dbias = dbias_b.swapaxes(0, 1).reshape(batch, num_k * block)[:, :length]
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        dbias.astype(kv_bias.dtype),
    )


flash_attention_tiled.defvjp(_fwd, _bwd)
