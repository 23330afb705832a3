"""Attention modules.

Capability parity with replay/nn/attention.py:6 (Differential Transformer attention,
arXiv 2410.05258: dual-softmax with a learned lambda and per-head RMSNorm) plus the
standard multi-head attention used by the SASRec encoder
(replay/nn/sequential/sasrec/transformer.py uses torch MultiheadAttention).

Both modules take an ADDITIVE float mask [B, 1, L, L] (see replay_tpu.nn.mask) and are
pure jnp — einsum contractions map straight onto the MXU and XLA fuses the
mask+softmax chain. Sequence-parallel ring attention reuses these shapes
(replay_tpu.parallel.ring).
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np


TILED_BLOCK = 512  # query and key/value rows of one tile on the "tiled" route (PERF.md, PR 31)


def dot_product_attention(
    q: jnp.ndarray,  # [B, H, L, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,  # additive [B, 1, L, L]; None on the "tiled"/"ring" routes
    use_flash=False,  # False | True (single-block kernel) | "tiled" | "ring"
    padding_mask: jnp.ndarray = None,  # [B, L] bool, required for "tiled"/"ring"
    causal: bool = True,
    return_weights: bool = False,  # also return the [B, H, L, L] softmax weights
    window: Optional[int] = None,  # "tiled" only: query i sees keys i - window < j <= i
) -> jnp.ndarray:
    if return_weights and use_flash:
        # the flash kernels never materialize the weights — that is the point
        msg = "return_weights=True requires the standard (use_flash=False) route"
        raise ValueError(msg)
    if use_flash == "ring":
        # sequence-parallel exact attention: the L axis stays sharded over the
        # trainer mesh's seq axis, KV blocks rotate with ppermute, and no
        # [B, 1, L, L] mask nor full-sequence gather ever materializes
        # (replay_tpu.parallel.ring; Ring Attention, arXiv 2310.01889)
        from replay_tpu.parallel.ring import ring_attention
        from replay_tpu.parallel.sharding import active_scope

        if padding_mask is None:
            msg = "use_flash='ring' needs the [B, L] padding_mask"
            raise ValueError(msg)
        if mask is not None:
            msg = "use_flash='ring' cannot honor an additive mask; pass mask=None"
            raise ValueError(msg)
        scope = active_scope()
        if scope is None:
            msg = (
                "use_flash='ring' resolves its mesh and sequence axis from the "
                "trainer's sharding scope — train/score through "
                "replay_tpu.nn.Trainer(sharding_rules=...), or wrap the apply "
                "in replay_tpu.parallel.sharding.sharding_scope(rules, mesh)"
            )
            raise RuntimeError(msg)
        rules, mesh = scope
        seq_axis = rules.mesh_axis("length")
        if seq_axis is None or isinstance(seq_axis, tuple):
            msg = (
                f"use_flash='ring' needs the 'length' rule to name ONE mesh "
                f"axis; the active table maps it to {seq_axis!r}"
            )
            raise ValueError(msg)
        batch_axis = rules.mesh_axis("batch")
        if isinstance(batch_axis, tuple) or (
            batch_axis is not None
            and (q.shape[0] % mesh.shape[batch_axis] or rules.axis_size(mesh, "batch") <= 1)
        ):
            batch_axis = None  # replicate rows inside the ring shard_map
        out = ring_attention(
            q.swapaxes(-3, -2),  # [B, H, L, D] -> [B, L, H, D]
            k.swapaxes(-3, -2),
            v.swapaxes(-3, -2),
            mesh,
            axis_name=seq_axis,
            causal=causal,
            padding_mask=padding_mask,
            data_axis=batch_axis,
        )
        return out.swapaxes(-3, -2).astype(q.dtype)
    if use_flash == "tiled":
        # length-tiled kernel: O(L·block) memory, mask computed in-kernel from
        # (causal, padding) — callers skip building the [B, 1, L, L] tensor
        from replay_tpu.ops.flash_attention import pallas_interpret
        from replay_tpu.ops.flash_tiled import flash_attention_tiled, padding_mask_bias

        if padding_mask is None:
            msg = "use_flash='tiled' needs the [B, L] padding_mask"
            raise ValueError(msg)
        if mask is not None:
            # the tiled kernel reconstructs attention structure from (causal,
            # padding) alone; accepting a custom additive mask here would
            # silently drop whatever else it encodes (e.g. TiSASRec's
            # interval bias)
            msg = "use_flash='tiled' cannot honor an additive mask; pass mask=None"
            raise ValueError(msg)
        return flash_attention_tiled(
            q, k, v, padding_mask_bias(padding_mask), causal, TILED_BLOCK, TILED_BLOCK,
            pallas_interpret(), window,
        ).astype(q.dtype)
    if window is not None:
        msg = "only use_flash='tiled' computes a band; the other routes take an additive mask"
        raise ValueError(msg)
    if use_flash:
        # pallas fused kernel: no [B, H, L, L] HBM materialization
        from replay_tpu.ops.flash_attention import (
            MAX_SINGLE_BLOCK_LENGTH,
            flash_attention,
            pallas_interpret,
        )

        if q.shape[-2] > MAX_SINGLE_BLOCK_LENGTH:
            msg = (
                f"use_flash=True holds one whole [L, L] block in VMEM and the "
                f"TPU compiler refuses it past L={MAX_SINGLE_BLOCK_LENGTH} "
                f"(got L={q.shape[-2]}); use use_flash='tiled' for long sequences"
            )
            raise ValueError(msg)
        return flash_attention(q, k, v, mask, interpret=pallas_interpret()).astype(q.dtype)
    scale = 1.0 / jnp.sqrt(jnp.array(q.shape[-1], dtype=q.dtype))
    if k.shape[-3] != q.shape[-3]:
        # grouped-query heads: each of the Hkv key/value heads serves H/Hkv
        # query heads (query head h reads key/value head h // (H/Hkv)); the
        # group is an einsum axis, so K and V are never repeated in memory
        return _grouped_query_attention(q, k, v, mask, scale, return_weights)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + mask.astype(q.dtype)
    weights = nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    if return_weights:
        return out, weights
    return out


def _grouped_query_attention(q, k, v, mask, scale, return_weights: bool):
    batch, heads, length, head_dim = q.shape
    kv_heads = k.shape[-3]
    if heads % kv_heads:
        msg = f"{heads} query heads do not divide over {kv_heads} key/value heads"
        raise ValueError(msg)
    grouped = q.reshape(batch, kv_heads, heads // kv_heads, length, head_dim)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", grouped, k) * scale
    weights = nn.softmax(scores + mask.astype(q.dtype)[:, :, None], axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", weights, v).reshape(q.shape)
    if return_weights:
        return out, weights.reshape(batch, heads, length, length)
    return out


def rotary_embedding(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: Optional[float] = None,
    *,
    inv_freq: Optional[jnp.ndarray] = None,  # [D/2] in place of theta's
    attention_factor: float = 1.0,
) -> jnp.ndarray:
    """Rotary positions on ``x`` [..., L, D]: the half-split ("rotate half")
    form, pair i of (x[i], x[i + D/2]) turned by ``positions * inv_freq[i]``,
    ``inv_freq`` given or ``theta**(-2i/D)``; cos and sin are scaled by
    ``attention_factor`` (YaRN's; on q and k alike, so scores by its square).
    Angles and the rotation are float32; the result takes ``x``'s dtype. Being
    relative, it needs no table and no maximum length."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [L, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    x32 = x.astype(jnp.float32)
    first, second = x32[..., :half], x32[..., half:]
    rotated = jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def yarn_correction_range(head_dim, theta, original_max_position, beta_fast, beta_slow):
    """(low, high): the pairs between which YaRN blends. ``dim(r)``, the pair
    that turns ``r`` times over the original context, is ``D ln(L0 / (2 pi r)) /
    (2 ln theta)``; low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)),
    clipped to the pairs there are."""
    turns = lambda r: head_dim * math.log(original_max_position / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(theta)
    )
    return max(math.floor(turns(beta_fast)), 0), min(math.ceil(turns(beta_slow)), head_dim - 1)


def yarn_inv_freq(head_dim, theta, factor, original_max_position, beta_fast=32.0, beta_slow=1.0):
    """YaRN's frequencies [D/2] (arXiv 2309.00071, as ``transformers`` computes
    them): pairs faster than ``low`` keep ``theta**(-2i/D)`` (extrapolation),
    pairs slower than ``high`` are divided by ``factor`` (interpolation), and
    those between blend linearly. float64 here, float32 out: a constant."""
    low, high = yarn_correction_range(head_dim, theta, original_max_position, beta_fast, beta_slow)
    pairs = np.arange(head_dim // 2, dtype=np.float64)
    ramp = np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = theta ** (-2.0 * pairs / head_dim)
    return jnp.asarray(plain * ((1.0 - ramp) + ramp / factor), jnp.float32)


def rotary_arguments(head_dim: int, theta: float, scaling: Optional[Mapping[str, Any]]):
    """:func:`rotary_embedding`'s keyword arguments for one layer type's
    ``rope_parameters``: ``None`` or ``rope_type: default`` is the one-theta form."""
    if not scaling or scaling.get("rope_type", "default") == "default":
        return {"theta": theta}
    if scaling["rope_type"] != "yarn":
        msg = f"unknown rope_type {scaling['rope_type']!r}; known: default, yarn"
        raise ValueError(msg)
    factor = scaling["factor"]
    return {
        "inv_freq": yarn_inv_freq(
            head_dim, scaling.get("rope_theta", theta), factor,
            scaling["original_max_position_embeddings"],
            scaling.get("beta_fast", 32.0), scaling.get("beta_slow", 1.0),
        ),
        "attention_factor": float(scaling.get("attention_factor") or 0.1 * math.log(factor) + 1.0),
    }


class MultiHeadAttention(nn.Module):
    """Standard multi-head self-attention with an additive mask.

    ``use_flash=True`` routes through the single-block pallas kernel
    (replay_tpu.ops.flash_attention, L up to ~1024); ``use_flash="tiled"``
    through the length-tiled kernel (replay_tpu.ops.flash_tiled) — the long-L
    path, which never materializes anything O(L²) and therefore takes the raw
    ``padding_mask`` + ``causal`` flag instead of ``mask``."""

    num_heads: int
    dropout_rate: float = 0.0
    use_flash: Any = False  # False | True | "tiled"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        mask: jnp.ndarray,
        deterministic: bool = True,
        padding_mask: jnp.ndarray = None,
        causal: bool = True,
    ) -> jnp.ndarray:
        dim = x.shape[-1]
        if dim % self.num_heads:
            msg = f"embedding dim {dim} not divisible by {self.num_heads} heads"
            raise ValueError(msg)
        head_dim = dim // self.num_heads

        def split(name):
            proj = nn.Dense(dim, dtype=self.dtype, name=name)(x)
            return proj.reshape(*x.shape[:-1], self.num_heads, head_dim).swapaxes(-3, -2)

        q, k, v = split("query"), split("key"), split("value")
        # model-health capture (replay_tpu.obs.health): when the caller made
        # the `intermediates` collection mutable AND the standard einsum route
        # runs (the flash kernels never materialize the weights), sow the
        # per-head mean attention entropy. Python-level guard: the disabled
        # step lowers to byte-identical HLO; the sowed [H] vector is dead code
        # (DCE'd by XLA) for consumers that capture but drop it.
        if not self.use_flash and self.is_mutable_collection("intermediates"):
            out, weights = dot_product_attention(
                q, k, v, mask, causal=causal, return_weights=True
            )
            w32 = weights.astype(jnp.float32)
            entropy = -jnp.sum(w32 * jnp.log(w32 + 1e-9), axis=-1)  # [B, H, L]
            if padding_mask is not None:
                # mean over VALID query rows only: padded rows are forced
                # one-hot by the diagonal rescue (entropy 0) and would drag
                # the signal toward the "collapsed attention" reading on
                # heavily padded batches
                valid = padding_mask.astype(w32.dtype)  # [B, L]
                per_head = jnp.sum(entropy * valid[:, None, :], axis=(0, 2)) / jnp.maximum(
                    jnp.sum(valid), 1.0
                )
            else:
                per_head = jnp.mean(entropy, axis=(0, 2))
            self.sow("intermediates", "attention_entropy", per_head)
        else:
            out = dot_product_attention(
                q, k, v, mask, use_flash=self.use_flash,
                padding_mask=padding_mask, causal=causal,
            )
        out = out.swapaxes(-3, -2).reshape(*x.shape[:-1], dim)
        out = nn.Dense(dim, dtype=self.dtype, name="out")(out)
        return nn.Dropout(self.dropout_rate, deterministic=deterministic)(out)


class GroupedQueryAttention(nn.Module):
    """Self-attention with fewer key/value heads than query heads, rotary
    positions and an RMS norm over each head's width on q and k (bias-free
    projections; the attention layer of the layer-pattern block stack,
    replay_tpu.nn.blocks); ``qk_norm=False`` leaves both norms out (the
    ``ouro`` configuration carries none). Positions are the indices in the window: rotary
    scores depend on their differences only, so left padding shifts nothing.
    ``rope_scaling``: the layer type's ``rope_parameters`` (``rope_type`` ``yarn``
    with its factor, original length, betas and attention factor; ``None``: the
    one-theta form).

    Two routes. With an additive ``mask`` [B, 1, L, L]: the standard route of
    :func:`dot_product_attention`. With ``mask=None`` and the [B, L]
    ``padding_mask``: the fused, length-tiled route (replay_tpu.ops.flash_tiled),
    causal, key/value heads at their own count, and with ``window`` the band
    ``0 <= i - j < window`` whose outside blocks are skipped, never masked; the
    route's block counts are sown into ``counters`` (``attention_blocks_visited``
    [forward, backward]: kv-block products per row and query head;
    ``attention_blocks_needed``: the visible pairs over one block's area, what
    a route with no rounding would compute: :func:`sow_block_counts`). Only that
    route has a band.
    """

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    window: Optional[int] = None
    rope_scaling: Optional[Mapping[str, Any]] = None
    qk_norm: bool = True

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, mask: Optional[jnp.ndarray], padding_mask: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        length = x.shape[-2]

        def heads_of(name, count):
            proj = nn.Dense(count * self.head_dim, use_bias=False, dtype=self.dtype, name=name)(x)
            return proj.reshape(*x.shape[:-1], count, self.head_dim)

        def normed(name, t):
            return RMSNorm(self.norm_eps, dtype=self.dtype, name=name)(t) if self.qk_norm else t

        q = normed("q_norm", heads_of("query", self.num_heads))
        k = normed("k_norm", heads_of("key", self.num_kv_heads))
        v = heads_of("value", self.num_kv_heads)
        positions = jnp.arange(length)
        q, k, v = (t.swapaxes(-3, -2) for t in (q, k, v))  # [B, H, L, D]
        rotary = rotary_arguments(self.head_dim, self.rope_theta, self.rope_scaling)
        q = rotary_embedding(q, positions, **rotary)
        k = rotary_embedding(k, positions, **rotary)
        if mask is None:
            out = dot_product_attention(
                q, k, v, None, use_flash="tiled", padding_mask=padding_mask, window=self.window
            )
            sow_block_counts(self, length, self.window)
        else:
            out = dot_product_attention(q, k, v, mask, window=self.window)
        out = out.swapaxes(-3, -2).reshape(*x.shape[:-1], self.num_heads * self.head_dim)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype, name="out")(out)


def sow_block_counts(module: nn.Module, length: int, window: Optional[int] = None) -> None:
    """The fused route's schedule at this length into ``module``'s ``counters``
    (what :class:`GroupedQueryAttention`'s docstring names)."""
    from replay_tpu.ops.flash_tiled import block_counts

    counted = block_counts(length, TILED_BLOCK, TILED_BLOCK, True, window)
    visited = jnp.array([counted["visited"]] * 2, jnp.int32)  # one schedule, both directions
    needed = jnp.float32(counted["needed"] / counted["block_area"])
    latest = {"reduce_fn": lambda _, new: new, "init_fn": lambda: None}  # one value a step
    module.sow("counters", "attention_blocks_visited", visited, **latest)
    module.sow("counters", "attention_blocks_needed", needed, **latest)


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) with a decoupled rotary key, training
    side: keys and values are made from ONE ``latent_dim``-wide vector a
    position, and position enters through a ``rope_head_dim``-wide part of q and
    k that ALL heads share on the key side (source of the equations: the public
    ``deepseek_v3`` configuration of
    https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json,
    ``q_lora_rank`` null). Per position, H heads, every projection bias-free:

        q       = W_q x               -> [H, nope + rope], split q_nope | q_rope
        a       = W_kva x             -> [latent + rope]
        c       = rms(a[:latent])     a learned scale;  k_rope = a[latent:], ONE head
        c W_kvb                       -> [H, nope + value], split k_nope | v
        q_rope, k_rope <- rotary (half-split pairing; no other dim turns)
        k       = [k_nope | k_rope for every head]
        o       = softmax_causal(q k^T / sqrt(nope + rope)) v      -> [H, value]
        y       = W_o o

    No per-head norm on q or k. Always on the fused, length-tiled route
    (replay_tpu.ops.flash_tiled: float32 softmax statistics, causal, padding by
    ``padding_mask``), whose kernels carry the value width (``value_head_dim``)
    apart from the query/key width (``nope_head_dim + rope_head_dim``); the
    rotary key is broadcast over the heads before the call (PERF.md, PR 33). The
    route's block counts are sown as :class:`GroupedQueryAttention` sows them.
    The latent per-user cache and the absorbed decode path are not here.
    """

    num_heads: int
    latent_dim: int
    nope_head_dim: int
    rope_head_dim: int
    value_head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, padding_mask: jnp.ndarray) -> jnp.ndarray:
        length, heads = x.shape[-2], self.num_heads
        nope, rope, value = self.nope_head_dim, self.rope_head_dim, self.value_head_dim
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)  # noqa: E731

        q = dense(heads * (nope + rope), "query")(x).reshape(*x.shape[:-1], heads, nope + rope)
        down = dense(self.latent_dim + rope, "kv_down")(x)
        latent = RMSNorm(self.norm_eps, dtype=self.dtype, name="kv_norm")(down[..., : self.latent_dim])
        up = dense(heads * (nope + value), "kv_up")(latent).reshape(*x.shape[:-1], heads, nope + value)
        q, up = q.swapaxes(-3, -2), up.swapaxes(-3, -2)  # [B, H, L, .]
        positions = jnp.arange(length)
        q_rope = rotary_embedding(q[..., nope:], positions, self.rope_theta)
        k_rope = rotary_embedding(down[..., None, :, self.latent_dim :], positions, self.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k_rope = jnp.broadcast_to(k_rope, (*k_rope.shape[:-3], heads, length, rope))
        k = jnp.concatenate([up[..., :nope], k_rope], axis=-1)
        out = dot_product_attention(
            q, k, up[..., nope:], None, use_flash="tiled", padding_mask=padding_mask
        )
        sow_block_counts(self, length)
        out = out.swapaxes(-3, -2).reshape(*x.shape[:-1], heads * value)
        return dense(x.shape[-1], "out")(out)


class RMSNorm(nn.Module):
    """RMS normalization over the last axis (no mean subtraction)."""

    epsilon: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        norm = jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.epsilon)
        return x / norm * scale.astype(x.dtype)


class MultiHeadDifferentialAttention(nn.Module):
    """Differential attention: softmax(Q1K1) - lambda * softmax(Q2K2) per head.

    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, with per-head RMSNorm and
    the (1 - lambda_init) output scaling from the paper.
    """

    num_heads: int
    lambda_init: float = 0.8
    dropout_rate: float = 0.0
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, mask: jnp.ndarray, deterministic: bool = True
    ) -> jnp.ndarray:
        dim = x.shape[-1]
        if dim % (2 * self.num_heads):
            msg = f"embedding dim {dim} must be divisible by 2*num_heads ({2 * self.num_heads})"
            raise ValueError(msg)
        head_dim = dim // (2 * self.num_heads)

        def split(name):
            proj = nn.Dense(dim, use_bias=False, dtype=self.dtype, name=name)(x)
            # two attention maps per head: [B, 2H, L, D/2H]
            return proj.reshape(*x.shape[:-1], 2 * self.num_heads, head_dim).swapaxes(-3, -2)

        q, k = split("query"), split("key")
        v_proj = nn.Dense(dim, use_bias=False, dtype=self.dtype, name="value")(x)
        v = v_proj.reshape(*x.shape[:-1], self.num_heads, 2 * head_dim).swapaxes(-3, -2)

        scale = 1.0 / jnp.sqrt(jnp.array(head_dim, dtype=x.dtype))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + mask.astype(x.dtype)
        weights = nn.softmax(scores, axis=-1)
        w1 = weights[:, 0::2]  # [B, H, L, L]
        w2 = weights[:, 1::2]

        init = nn.initializers.normal(stddev=0.1)
        lq1 = self.param("lambda_q1", init, (head_dim,))
        lk1 = self.param("lambda_k1", init, (head_dim,))
        lq2 = self.param("lambda_q2", init, (head_dim,))
        lk2 = self.param("lambda_k2", init, (head_dim,))
        lam = (
            jnp.exp(jnp.dot(lq1, lk1)) - jnp.exp(jnp.dot(lq2, lk2)) + self.lambda_init
        ).astype(x.dtype)

        attn = w1 - lam * w2
        out = jnp.einsum("bhqk,bhkd->bhqd", attn, v)  # [B, H, L, 2*head_dim]
        out = RMSNorm(dtype=self.dtype, name="head_norm")(out)
        out = out * (1.0 - self.lambda_init)
        out = out.swapaxes(-3, -2).reshape(*x.shape[:-1], dim)
        out = nn.Dense(dim, use_bias=False, dtype=self.dtype, name="out")(out)
        return nn.Dropout(self.dropout_rate, deterministic=deterministic)(out)
