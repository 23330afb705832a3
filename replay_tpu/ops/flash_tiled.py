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

Grouped-query heads and a band. K and V keep their own head count: query head
``h`` reads key/value head ``h // (H / Hkv)`` through the block index map, so
they are never repeated in memory. With ``window`` the mask is the band
``0 <= i - j < window`` (causal, and at most ``window`` keys back), computed
in-kernel from block indices like the causal mask. The kv axis of the grid is
only as long as the widest run of kv blocks any query block can see
(:func:`kv_block_range`): a query block's step ``s`` reads kv block ``first(i) +
s``, so blocks wholly outside the band are neither fetched nor multiplied.

Training: ``jax.custom_vjp`` with two backward kernels over the (query block, kv
block) pairs of the SAME schedule, each recomputing a pair's probabilities from
the saved logsumexp: dq walks a query block's kv blocks as the forward does; dk
and dv walk, for a kv block of one key/value head, the group's query heads and
the query blocks that see it (:func:`q_block_range`). Never O(L²), never a pair
outside the band. :func:`block_counts` gives what both directions visit, for the
counters of the layer that calls the route.

Value width apart from key width. Scores contract over ``q.shape[-1]`` (which is
``k``'s too, and sets the softmax scale); ``v``, the output and its cotangent
are ``v.shape[-1]`` wide. Latent attention has keys of 192 (128 + a 64-wide
rotary part) over values of 128; nothing is padded to the wider of the two.

Beyond-parity: the reference has no custom kernels; its torch path
materializes [B, H, L, L] (SURVEY.md §2.3). The mesh-sharded regime is ring
attention (replay_tpu/parallel/ring.py); this kernel is the within-chip story.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def kv_block_range(qi, block_q, block_k, num_k, causal, window, xp=jnp):
    """(first, last) kv block that holds a key visible from query block ``qi``
    (``qi`` and the result: traced scalars with ``xp=jnp``, whole arrays with
    ``xp=numpy``). Above the diagonal nothing is visible; with a window nothing
    older than ``window - 1`` keys before the block's first row."""
    last = qi * 0 + (num_k - 1)
    if causal:
        last = xp.minimum((qi * block_q + block_q - 1) // block_k, last)
    first = qi * 0
    if window is not None:
        first = xp.maximum(qi * block_q - (window - 1), 0) // block_k
    return first, last


def _blocks(length, block_q, block_k):
    """Block sizes as run (never longer than the sequence) and how many of each."""
    block_q, block_k = min(block_q, max(length, 1)), min(block_k, max(length, 1))
    return block_q, block_k, -(-length // block_q), -(-length // block_k)


def block_counts(length, block_q=256, block_k=256, causal=True, window=None):
    """What the route visits for one (batch row, query head) at this length:
    ``visited`` kv-block products of ``block_area`` score entries each, in the
    forward kernel and again in each backward kernel (one schedule, so one number),
    and ``needed``, the visible (query, key) pairs themselves, padding aside."""
    block_q, block_k, num_q, num_k = _blocks(length, block_q, block_k)
    first, last = kv_block_range(np.arange(num_q), block_q, block_k, num_k, causal, window, np)
    reach = length if window is None else min(window, length)
    needed = reach * length - reach * (reach - 1) // 2 if causal else length * length
    return {"visited": int((last - first + 1).sum()), "block_area": block_q * block_k,
            "needed": int(needed)}


def _kv_walk(num_q, block_q, block_k, num_k, causal, window):
    """(steps of the grid's kv axis, index map ``(query block, step) -> kv block``):
    step ``s`` of query block ``i`` reads kv block ``first(i) + s``; past the last
    visible block the index stays where it was, so nothing new is fetched."""
    first, last = kv_block_range(np.arange(num_q), block_q, block_k, num_k, causal, window, np)

    def kv_block(i, s):
        first, last = kv_block_range(i, block_q, block_k, num_k, causal, window)
        return jnp.minimum(first + s, last)

    return int((last - first + 1).max()), kv_block


def _visible(scores_shape, qi, ki, block_q, block_k, causal, window):
    """The band inside one block, or None where every pair is visible."""
    if not causal:
        return None
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, scores_shape, 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, scores_shape, 1)
    seen = cols <= rows
    if window is not None:
        seen = seen & (rows - cols < window)
    return seen


def _kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref, m_ref, l_ref, acc_ref,
            *, block_q, block_k, num_k, steps, causal, window):
    step = pl.program_id(3)
    qi = pl.program_id(2)
    first, last = kv_block_range(qi, block_q, block_k, num_k, causal, window)
    ki = first + step

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate():
        # operands go to the MXU in the dtype they came in (bfloat16 under
        # precision="bf16"); products accumulate in float32
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]  # [bq, D], [bk, D], [bk, D]
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        scores = scores + bias_ref[0]  # [1, bk] per-key bias (padding)
        seen = _visible(scores.shape, qi, ki, block_q, block_k, causal, window)
        if seen is not None:
            scores = jnp.where(seen, scores, NEG_INF)

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
            probs.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # steps past this query block's last visible kv block (above the
        # diagonal; a short band at the top of the sequence) run no product;
        # init/finalize still run every step
        pl.when(ki <= last)(_accumulate)
    else:
        _accumulate()

    @pl.when(step == steps - 1)
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


def _forward(q, k, v, kv_bias, causal, block_q, block_k, interpret, window=None):
    batch, heads, length, dim = q.shape
    kv_heads, value_dim = k.shape[1], v.shape[-1]
    if heads % kv_heads:
        msg = f"{heads} query heads do not divide over {kv_heads} key/value heads"
        raise ValueError(msg)
    if k.shape[-1] != dim:
        msg = f"queries are {dim} wide and keys {k.shape[-1]}: scores contract over one width"
        raise ValueError(msg)
    if window is not None and not causal:
        msg = "a window is a causal band (0 <= i - j < window); causal=False has none"
        raise ValueError(msg)
    group = heads // kv_heads
    block_q, block_k, _, _ = _blocks(length, block_q, block_k)
    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    # the bias rides as [B, 1, Lk]: Mosaic wants a block's last two dims to be
    # (8, 128)-aligned or the whole array's, and a (1, block_k) block of a
    # [B, Lk] array is neither once B > 1
    bias = _pad_to(kv_bias.astype(jnp.float32), 1, block_k, value=NEG_INF)[:, None, :]
    lq, lk = qp.shape[2], kp.shape[2]
    num_q, num_k = lq // block_q, lk // block_k
    steps, kv_block = _kv_walk(num_q, block_q, block_k, num_k, causal, window)

    grid = (batch, heads, num_q, steps)
    qspec = pl.BlockSpec((1, 1, block_q, dim), lambda b, h, i, s: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block_k, dim), lambda b, h, i, s: (b, h // group, kv_block(i, s), 0))
    vspec = pl.BlockSpec((1, 1, block_k, value_dim), lambda b, h, i, s: (b, h // group, kv_block(i, s), 0))
    bspec = pl.BlockSpec((1, 1, block_k), lambda b, h, i, s: (b, 0, kv_block(i, s)))
    out_spec = pl.BlockSpec((1, 1, block_q, value_dim), lambda b, h, i, s: (b, h, i, 0))
    lse_spec = pl.BlockSpec((1, 1, block_q, 128), lambda b, h, i, s: (b, h, i, 0))

    from jax.experimental.pallas import tpu as pltpu

    scratch = [
        pltpu.VMEM((block_q, 128), jnp.float32),  # running max
        pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
        pltpu.VMEM((block_q, value_dim), jnp.float32),  # output accumulator
    ]
    out, lse = pl.pallas_call(
        partial(_kernel, block_q=block_q, block_k=block_k, num_k=num_k, steps=steps,
                causal=causal, window=window),
        grid=grid,
        in_specs=[qspec, kspec, vspec, bspec],
        out_specs=[out_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape[:-1] + (value_dim,), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, lq, 128), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(qp, kp, vp, bias)
    return out[:, :, :length], lse[:, :, :length, 0]


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_tiled(
    q: jnp.ndarray,  # [B, H, L, D]
    k: jnp.ndarray,  # [B, Hkv, L, D], H a multiple of Hkv
    v: jnp.ndarray,  # [B, Hkv, L, Dv]: the output is [B, H, L, Dv]
    kv_bias: jnp.ndarray,  # [B, L] additive per-key bias (0 valid / -1e30 pad)
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    window: Optional[int] = None,  # keys visible from query i: i - window < j <= i
) -> jnp.ndarray:
    """Length-tiled fused attention; VMEM and HBM stay O(L·block), not O(L²)."""
    out, _ = _forward(q, k, v, kv_bias, causal, block_q, block_k, interpret, window)
    return out


def padding_mask_bias(padding_mask: jnp.ndarray) -> jnp.ndarray:
    """[B, L] bool (True = real token) → the additive per-key bias row."""
    return jnp.where(padding_mask, 0.0, NEG_INF).astype(jnp.float32)


def q_block_range(ki, block_q, block_k, num_q, causal, window, xp=jnp):
    """(first, last) query block that sees a key of kv block ``ki``: the same
    pairs as :func:`kv_block_range`, listed by kv block."""
    first = (ki * block_k) // block_q if causal else ki * 0
    last = ki * 0 + (num_q - 1)
    if window is not None:
        last = xp.minimum((ki * block_k + block_k - 1 + window - 1) // block_q, last)
    return first, last


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref, dq_ref, acc_ref,
               *, block_q, block_k, num_k, steps, causal, window):
    """dq of one query block: the forward's walk over its kv blocks again, the
    probabilities made from the saved logsumexp [bq, 1]."""
    step, qi = pl.program_id(3), pl.program_id(2)
    first, last = kv_block_range(qi, block_q, block_k, num_k, causal, window)
    ki = first + step

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki <= last)
    def _accumulate():
        q, k, v, g = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], g_ref[0, 0]
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        contract_width = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q, k, contract_width, preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0]  # [1, bk]
        seen = _visible(s.shape, qi, ki, block_q, block_k, causal, window)
        if seen is not None:
            s = jnp.where(seen, s, NEG_INF)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse_ref[0, 0]))
        dp = jax.lax.dot_general(g, v, contract_width, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32) * scale

    @pl.when(step == steps - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, dbias_acc,
                *, block_q, block_k, num_q, steps, group, causal, window):
    """dk, dv and the key bias's cotangent of one kv block of one key/value head:
    over the ``group`` query heads that read it, and for each over the query
    blocks that see it. Scores are held keys-by-queries ([bk, bq]), so that the
    saved logsumexp and delta come in as rows [1, bq] and every product is a
    plain one."""
    inner, ki = pl.program_id(3), pl.program_id(2)
    first, last = q_block_range(ki, block_q, block_k, num_q, causal, window)
    qi = first + inner % steps

    @pl.when(inner == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dbias_acc[...] = jnp.zeros_like(dbias_acc)

    @pl.when(qi <= last)
    def _accumulate():
        q, k, v, g = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], g_ref[0, 0]
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        contract_width = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(k, q, contract_width, preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0]  # [bk, 1]
        if causal:
            keys = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = keys <= rows
            if window is not None:
                seen = seen & (rows - keys < window)
            s = jnp.where(seen, s, NEG_INF)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse_ref[0, 0]))  # lse [1, bq]
        dv_acc[...] += jnp.dot(p.astype(g.dtype), g, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, g, contract_width, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])
        dk_acc[...] += jnp.dot(ds.astype(q.dtype), q, preferred_element_type=jnp.float32) * scale
        dbias_acc[...] += jnp.broadcast_to(jnp.sum(ds, axis=1, keepdims=True), dbias_acc.shape)

    @pl.when(inner == group * steps - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)
        dbias_ref[0, 0] = dbias_acc[...]


def _fwd(q, k, v, kv_bias, causal, block_q, block_k, interpret, window):
    out, lse = _forward(q, k, v, kv_bias, causal, block_q, block_k, interpret, window)
    return out, (q, k, v, kv_bias, out, lse)


def _bwd(causal, block_q, block_k, interpret, window, residuals, g):
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, kv_bias, out, lse = residuals
    batch, heads, length, dim = q.shape
    kv_heads, value_dim = k.shape[1], v.shape[-1]
    group = heads // kv_heads
    block_q, block_k, num_q, num_k = _blocks(length, block_q, block_k)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B, H, L]
    # rows the padding adds: q and g are zero there, so they weigh nothing
    qp, gp = _pad_to(q, 2, block_q), _pad_to(g.astype(q.dtype), 2, block_q)
    kp, vp = _pad_to(k, 2, block_k), _pad_to(v, 2, block_k)
    lse_p, delta_p = _pad_to(lse, 2, block_q), _pad_to(delta, 2, block_q)
    bias_p = _pad_to(kv_bias.astype(jnp.float32), 1, block_k, value=NEG_INF)
    lq, lk = qp.shape[2], kp.shape[2]

    # -- dq: grid and walk of the forward
    steps, kv_block = _kv_walk(num_q, block_q, block_k, num_k, causal, window)
    rows_of = lambda block, width, index: pl.BlockSpec((1, 1, block, width), index)  # noqa: E731
    q_index = lambda b, h, i, s: (b, h, i, 0)  # noqa: E731
    kv_index = lambda b, h, i, s: (b, h // group, kv_block(i, s), 0)  # noqa: E731
    q_rows, g_rows = rows_of(block_q, dim, q_index), rows_of(block_q, value_dim, q_index)
    q_column = pl.BlockSpec((1, 1, block_q, 1), q_index)
    k_rows, v_rows = rows_of(block_k, dim, kv_index), rows_of(block_k, value_dim, kv_index)
    dq = pl.pallas_call(
        partial(_dq_kernel, block_q=block_q, block_k=block_k, num_k=num_k, steps=steps,
                causal=causal, window=window),
        grid=(batch, heads, num_q, steps),
        in_specs=[q_rows, k_rows, v_rows,
                  pl.BlockSpec((1, 1, block_k), lambda b, h, i, s: (b, 0, kv_block(i, s))),
                  g_rows, q_column, q_column],
        out_specs=q_rows,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dim), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, bias_p[:, None, :], gp, lse_p[..., None], delta_p[..., None])

    # -- dk, dv, dbias: per kv block, over the group's heads and the query blocks that see it
    first, last = q_block_range(np.arange(num_k), block_q, block_k, num_q, causal, window, np)
    steps = int((last - first + 1).max())

    def q_block(j, t):
        first, last = q_block_range(j, block_q, block_k, num_q, causal, window)
        return jnp.minimum(first + t % steps, last)

    head = lambda h, t: h * group + t // steps  # noqa: E731
    q_index = lambda b, h, j, t: (b, head(h, t), q_block(j, t), 0)  # noqa: E731
    kv_index = lambda b, h, j, t: (b, h, j, 0)  # noqa: E731
    q_rows, g_rows = rows_of(block_q, dim, q_index), rows_of(block_q, value_dim, q_index)
    q_row = pl.BlockSpec((1, 1, 1, block_q), lambda b, h, j, t: (b, head(h, t), 0, q_block(j, t)))
    k_rows, v_rows = rows_of(block_k, dim, kv_index), rows_of(block_k, value_dim, kv_index)
    dk, dv, dbias = pl.pallas_call(
        partial(_dkv_kernel, block_q=block_q, block_k=block_k, num_q=num_q, steps=steps,
                group=group, causal=causal, window=window),
        grid=(batch, kv_heads, num_k, group * steps),
        in_specs=[q_rows, k_rows, v_rows,
                  pl.BlockSpec((1, block_k, 1), lambda b, h, j, t: (b, j, 0)),
                  g_rows, q_row, q_row],
        out_specs=[k_rows, v_rows,
                   pl.BlockSpec((1, 1, block_k, 128), kv_index)],
        out_shape=[jax.ShapeDtypeStruct(kp.shape, k.dtype), jax.ShapeDtypeStruct(vp.shape, v.dtype),
                   jax.ShapeDtypeStruct((batch, kv_heads, lk, 128), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, dim), jnp.float32),
                        pltpu.VMEM((block_k, value_dim), jnp.float32),
                        pltpu.VMEM((block_k, 128), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, bias_p[:, :, None], gp, lse_p[:, :, None, :], delta_p[:, :, None, :])
    dbias = jnp.sum(dbias[..., 0], axis=1)  # over the key/value heads
    return (
        dq[:, :, :length], dk[:, :, :length], dv[:, :, :length],
        dbias[:, :length].astype(kv_bias.dtype),
    )


flash_attention_tiled.defvjp(_fwd, _bwd)
