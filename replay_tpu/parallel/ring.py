"""Ring attention: exact attention over sequences sharded across the mesh.

The reference framework has NO sequence parallelism (SURVEY.md §2.9 — session
lengths are managed by trimming/windowing). This module is the TPU-native
long-context extension the build plan calls first-class: sequences are sharded
over a mesh axis, and attention runs blockwise while key/value blocks rotate
around the ring with ``jax.lax.ppermute`` over ICI — memory per chip stays
O(L_local²-ish) and no all-gather of the full sequence ever materializes
(Ring Attention, arXiv 2310.01889; the pallas_guide.md collective pattern).

Numerics: an online-softmax accumulator (running max / denominator / weighted
sum — the flash-attention recurrence) makes the blockwise result exactly equal
to full softmax attention. Causality across blocks is resolved from ring
positions: the block held after ``s`` hops is the one ``s`` positions behind on
the ring, so a query block attends it fully when it is strictly earlier, with a
triangular mask when it is its own, and not at all when later.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attention(q, k, v, bias, state):
    """One blockwise online-softmax update.

    q: [B, Lq, H, D]; k/v: [B, Lk, H, D]; bias: [B, 1, Lq, Lk]-broadcastable
    additive mask. state = (o [B, Lq, H, D], m [B, Lq, H], l [B, Lq, H]).
    """
    o, m, l = state
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    scores = scores + bias
    block_max = jnp.max(scores, axis=-1)  # [B, H, Lq]
    new_m = jnp.maximum(m, block_max.transpose(0, 2, 1))  # [B, Lq, H]
    correction = jnp.exp(m - new_m)
    probs = jnp.exp(scores - new_m.transpose(0, 2, 1)[:, :, :, None])  # [B, H, Lq, Lk]
    block_o = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    new_o = o * correction[..., None] + block_o
    new_l = l * correction + jnp.sum(probs, axis=-1).transpose(0, 2, 1)
    return new_o, new_m, new_l


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = False,
    padding_mask: Optional[jnp.ndarray] = None,
    data_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Exact multi-head attention with the sequence axis sharded over ``axis_name``.

    :param q, k, v: [B, L, H, D] GLOBAL arrays (sharded or to-be-sharded on L).
    :param padding_mask: optional [B, L] bool, True at real tokens.
    :param data_axis: mesh axis the BATCH dim stays sharded over (the DP×SP
        production layout — omitting it on a mesh whose batch is data-sharded
        would silently all-gather the batch into every ring shard).
    :return: [B, L, H, D] attention output, sharded like ``q``.
    """
    n_shards = mesh.shape[axis_name]
    if q.shape[1] % n_shards:
        msg = f"Sequence length {q.shape[1]} not divisible by {n_shards} ring shards"
        raise ValueError(msg)
    if data_axis is not None and q.shape[0] % mesh.shape[data_axis]:
        msg = (
            f"Batch {q.shape[0]} not divisible by the {mesh.shape[data_axis]}-way "
            f"{data_axis!r} axis"
        )
        raise ValueError(msg)
    local_len = q.shape[1] // n_shards

    def local_fn(q_blk, k_blk, v_blk, pad_blk):
        my_index = jax.lax.axis_index(axis_name)
        positions = jnp.arange(local_len)

        def make_bias(kv_owner, kv_pad):
            # additive mask for (my queries) x (kv_owner's keys): [B, 1, Lq, Lk]
            bias = jnp.zeros((local_len, local_len), q_blk.dtype)
            if causal:
                q_pos = my_index * local_len + positions[:, None]
                k_pos = kv_owner * local_len + positions[None, :]
                bias = jnp.where(k_pos <= q_pos, bias, NEG_INF)
            bias = bias[None, None, :, :]
            if kv_pad is not None:  # per-row key padding
                bias = bias + jnp.where(kv_pad, 0.0, NEG_INF)[:, None, None, :]
            return bias

        o = jnp.zeros_like(q_blk)
        m = jnp.full(q_blk.shape[:3], NEG_INF, q_blk.dtype)
        l = jnp.zeros(q_blk.shape[:3], q_blk.dtype)
        kv_k, kv_v, kv_pad = k_blk, v_blk, pad_blk
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        for step in range(n_shards):
            kv_owner = (my_index - step) % n_shards
            bias = make_bias(kv_owner, kv_pad)
            o, m, l = _block_attention(q_blk, kv_k, kv_v, bias, (o, m, l))
            if step + 1 < n_shards:  # rotate kv one hop around the ring
                kv_k = jax.lax.ppermute(kv_k, axis_name, perm)
                kv_v = jax.lax.ppermute(kv_v, axis_name, perm)
                if kv_pad is not None:
                    kv_pad = jax.lax.ppermute(kv_pad, axis_name, perm)
        return o / jnp.maximum(l, 1e-30)[..., None]

    pad = padding_mask if padding_mask is not None else jnp.ones(q.shape[:2], bool)
    spec = P(data_axis, axis_name, None, None)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec, P(data_axis, axis_name)),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, pad)


def full_attention_reference(q, k, v, causal=False, padding_mask=None):
    """Single-device full-softmax attention (the correctness oracle)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    length = q.shape[1]
    if causal:
        tri = jnp.tril(jnp.ones((length, length), bool))
        scores = jnp.where(tri[None, None], scores, NEG_INF)
    if padding_mask is not None:
        scores = jnp.where(padding_mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
