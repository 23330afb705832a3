"""One block definition, stacked by a layer pattern.

A block is (mixer kind, feed-forward kind) around a pre-norm residual stream:

    h = x + post(mixer(rms(x)))    mixer: "full_attention" | "sliding_attention" | "conv"
                                          | "latent_attention"
    y = h + post(ffn(rms(h)))      ffn:   dense SwiGLU | sparse experts [+ shared expert]

where ``post`` is the identity, or with ``sandwich`` an RMSNorm of its own after
each sublayer (``mixer_post_norm``, ``ffn_post_norm``: the "sandwich" norms of the
public ``ouro`` configuration, which also runs the whole stack several times over
one set of weights: replay_tpu.nn.sequential.hybrid.model, ``loop_steps``),
and a model is a list of mixer kinds (``layer_types``) with the number of
leading layers whose feed-forward is dense (``num_dense_layers``, which may be
0); every later layer routes over sparse experts. ``sliding_attention`` is
``full_attention`` over the last ``sliding_window`` keys only, always on the
fused route (replay_tpu.ops.flash_tiled: blocks outside the band are skipped);
full layers take that route too when ``fused_attention`` is set, and the
standard one (an additive [B, 1, L, L] mask) otherwise. Rotary parameters
arrive by layer type (``rope_scaling``: ``{layer type: rope_parameters}``). No absolute position table (attention carries
rotary positions, the convolution needs none), no bias, RMSNorm throughout. A
new mechanism is a new entry in :data:`MIXERS`, not a model file.

``latent_attention`` (replay_tpu.nn.attention.LatentAttention; the third public
configuration, ``deepseek_v3`` as
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
publishes it): keys and values from one ``kv_latent_dim``-wide vector a
position, a ``rope_head_dim``-wide rotary part beside the ``head_dim``-wide
rest of q and k, values ``value_head_dim`` wide; always on the fused route.
``shared_expert_dim`` > 0 adds to every sparse layer a dense SwiGLU of that
width that every token passes (``routed_share(h) + SwiGLU(h)``; computed whole
on every chip that holds a share of the routed experts), and the layer counts
the positions it multiplied (``shared_expert_tokens`` in ``counters``).

Padding: the stream is zero at padding positions on entry and is zeroed there
again after every block, so a mixer never reads them (``rms(0) = 0``; attention
masks them as keys besides) and the expert layer leaves them out of its dispatch.

``remat`` recomputes each block from its input on the way back (one
``jax.checkpoint`` a block, ``remat_policy`` choosing what it keeps:
``Trainer(remat_policy=...)``): the stream at each block's input is what a step
keeps of the stack.

Each layer kind runs under a ``jax.named_scope`` of its own (``attention``,
``window_attention``, ``latent_attention``, ``conv``, ``dense_ffn``, ``moe`` and,
its sibling, ``shared_expert``) so that a device trace splits by kind.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from replay_tpu.nn.attention import GroupedQueryAttention, LatentAttention, RMSNorm
from replay_tpu.nn.conv import GatedShortConv
from replay_tpu.nn.ffn import SwiGLU
from replay_tpu.nn.moe import SparseExperts
from replay_tpu.parallel.sharding import shard_activation

MIXERS = ("full_attention", "sliding_attention", "conv", "latent_attention")
ATTENTION_SCOPES = {"full_attention": "attention", "sliding_attention": "window_attention"}


def needs_mask(layer_types: Sequence[str], fused_attention: bool) -> bool:
    """Whether some layer takes the additive [B, 1, L, L] mask (the standard route)."""
    return not fused_attention and "full_attention" in layer_types


class PatternBlock(nn.Module):
    """One pre-norm block of the pattern: ``mixer`` names the sequence mixer,
    ``sparse`` picks the expert layer over the dense SwiGLU."""

    mixer: str
    sparse: bool
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    conv_kernel: int
    dense_dim: int
    expert_dim: int
    num_experts: int
    experts_held: int
    expert_offset: int
    experts_per_token: int
    routed_scale: float
    norm_eps: float
    dtype: Any = jnp.float32
    router: str = "sigmoid"
    sliding_window: Optional[int] = None
    fused_attention: bool = False
    rope_scaling: Optional[Mapping[str, Any]] = None  # this layer type's rope_parameters
    kv_latent_dim: Optional[int] = None  # latent_attention: the key/value latent's width
    rope_head_dim: Optional[int] = None  # latent_attention: the rotary part of q and k
    value_head_dim: Optional[int] = None  # latent_attention: a value head (None: head_dim)
    shared_expert_dim: int = 0  # 0: a sparse layer is its routed experts alone
    sandwich: bool = False  # an RMSNorm after each sublayer too (module docstring)
    qk_norm: bool = True  # attention layers: an RMSNorm over each head of q and k

    @nn.compact
    def __call__(self, x, attention_mask, padding_mask):
        norm = lambda name: RMSNorm(self.norm_eps, dtype=self.dtype, name=name)  # noqa: E731
        h = norm("mixer_norm")(x)
        if self.mixer in ATTENTION_SCOPES:
            sliding = self.mixer == "sliding_attention"
            if sliding and not self.sliding_window:
                msg = "a sliding_attention layer needs sliding_window"
                raise ValueError(msg)
            with jax.named_scope(ATTENTION_SCOPES[self.mixer]):
                h = GroupedQueryAttention(
                    num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim, rope_theta=self.rope_theta,
                    norm_eps=self.norm_eps, dtype=self.dtype,
                    window=self.sliding_window if sliding else None,
                    rope_scaling=self.rope_scaling, qk_norm=self.qk_norm, name="attention",
                )(h, None if sliding or self.fused_attention else attention_mask, padding_mask)
        elif self.mixer == "latent_attention":
            if not self.kv_latent_dim or not self.rope_head_dim:
                msg = "a latent_attention layer needs kv_latent_dim and rope_head_dim"
                raise ValueError(msg)
            with jax.named_scope("latent_attention"):
                h = LatentAttention(
                    num_heads=self.num_heads, latent_dim=self.kv_latent_dim,
                    nope_head_dim=self.head_dim, rope_head_dim=self.rope_head_dim,
                    value_head_dim=self.value_head_dim or self.head_dim,
                    rope_theta=self.rope_theta, norm_eps=self.norm_eps, dtype=self.dtype,
                    name="attention",
                )(h, padding_mask)
        elif self.mixer == "conv":
            with jax.named_scope("conv"):
                h = GatedShortConv(self.conv_kernel, dtype=self.dtype, name="conv")(h)
        else:
            msg = f"unknown layer type {self.mixer!r}; known: {MIXERS}"
            raise ValueError(msg)
        if self.sandwich:
            h = norm("mixer_post_norm")(h)
        x = x + h
        h = norm("ffn_norm")(x)
        if self.sparse:
            with jax.named_scope("moe"):
                routed = SparseExperts(
                    num_experts=self.num_experts, experts_held=self.experts_held,
                    expert_offset=self.expert_offset, top_k=self.experts_per_token,
                    hidden_dim=self.expert_dim, scale=self.routed_scale,
                    dtype=self.dtype, router=self.router, name="moe",
                )(h, token_mask=padding_mask)
            if self.shared_expert_dim:
                with jax.named_scope("shared_expert"):  # a sibling of `moe`, not inside it
                    routed = routed + SwiGLU(
                        self.shared_expert_dim, x.shape[-1], dtype=self.dtype, name="shared_expert"
                    )(h)
                self.sow(
                    "counters", "shared_expert_tokens", jnp.sum(padding_mask, dtype=jnp.int32),
                    reduce_fn=lambda _, new: new, init_fn=lambda: None,  # one value a step
                )
            h = routed
        else:
            with jax.named_scope("dense_ffn"):
                h = SwiGLU(self.dense_dim, x.shape[-1], dtype=self.dtype, name="dense_ffn")(h)
        if self.sandwich:
            h = norm("ffn_post_norm")(h)
        keep = padding_mask[..., None].astype(x.dtype)
        return shard_activation((x + h) * keep, "batch", "length", "embed")


class LayerPatternEncoder(nn.Module):
    """``len(layer_types)`` blocks, layer ``i`` mixing by ``layer_types[i]``; the
    first ``num_dense_layers`` feed forward densely, the rest through experts."""

    layer_types: Sequence[str]
    num_dense_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    conv_kernel: int = 3
    dense_dim: int = 256
    expert_dim: int = 64
    num_experts: int = 8
    experts_held: Optional[int] = None  # None: every expert lives here
    expert_offset: int = 0
    experts_per_token: int = 2
    routed_scale: float = 1.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    router: str = "sigmoid"
    sliding_window: Optional[int] = None
    fused_attention: bool = False
    rope_scaling: Optional[Mapping[str, Any]] = None  # {layer type: rope_parameters}
    kv_latent_dim: Optional[int] = None
    rope_head_dim: Optional[int] = None
    value_head_dim: Optional[int] = None
    shared_expert_dim: int = 0
    sandwich: bool = False
    qk_norm: bool = True
    remat: bool = False  # recompute each block from its input on the way back
    remat_policy: Any = None  # what a block's checkpoint keeps (None: nothing)

    @nn.compact
    def __call__(self, x, attention_mask, padding_mask):
        held = self.num_experts if self.experts_held is None else self.experts_held
        block = nn.remat(PatternBlock, policy=self.remat_policy) if self.remat else PatternBlock
        for i, mixer in enumerate(self.layer_types):
            x = block(
                mixer=mixer, sparse=i >= self.num_dense_layers,
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, rope_theta=self.rope_theta,
                conv_kernel=self.conv_kernel, dense_dim=self.dense_dim,
                expert_dim=self.expert_dim, num_experts=self.num_experts,
                experts_held=held, expert_offset=self.expert_offset,
                experts_per_token=self.experts_per_token, routed_scale=self.routed_scale,
                norm_eps=self.norm_eps, dtype=self.dtype, router=self.router,
                sliding_window=self.sliding_window, fused_attention=self.fused_attention,
                rope_scaling=(self.rope_scaling or {}).get(mixer),
                kv_latent_dim=self.kv_latent_dim, rope_head_dim=self.rope_head_dim,
                value_head_dim=self.value_head_dim, shared_expert_dim=self.shared_expert_dim,
                sandwich=self.sandwich, qk_norm=self.qk_norm, name=f"layer_{i}",
            )(x, attention_mask, padding_mask)
        return x
