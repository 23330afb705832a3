"""HybridRec: a next-item model over a layer-pattern block stack.

The generative-recommender setting: the backbone of a sparse-expert language
model (gated short convolutions with a full-attention layer every few blocks,
grouped-query rotary attention, sigmoid-routed SwiGLU experts) over an ITEM
vocabulary. The item catalog takes the place of the token vocabulary, the tied
item table scores the next item, and everything around the block stack is
SasRec's: the same ``schema=`` constructor and embedder, the same tying head,
``Trainer.fit`` with ``CE`` and the SASRec train transforms.

    x    = table[items] * keep                      no position table, no scaling
    x    = LayerPatternEncoder(x)                   replay_tpu.nn.blocks
    out  = rms(x)                                   the family's embedding norm
    logits = out . table[:num_items]^T

Source of the layer equations and of the default widths' names: the public
``lfm2_moe`` configuration (https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json).
``experts_held`` / ``expert_offset`` give this chip's share of each expert
layer (replay_tpu.nn.moe); the defaults hold every expert.

The same stack carries the window-and-full pattern of the public ``mellum``
configuration (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
``layer_types`` of ``sliding_attention`` and ``full_attention`` with
``sliding_window``, ``fused_attention`` (the full layers on the fused route
too), ``rope_scaling`` by layer type (YaRN on the full layers), ``router="softmax"``,
``num_dense_layers=0`` and ``tie_embeddings=False``: an output table of its
own, ``logits = rms(x) . output_table^T``, which ``get_item_weights()`` returns,
so the input table gets no gradient from the head.

And the latent-attention pattern of the public ``deepseek_v3`` configuration
that Moonlight publishes (https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json):
``layer_types`` of ``latent_attention`` with ``kv_latent_dim`` (``kv_lora_rank``),
``head_dim`` (``qk_nope_head_dim``), ``rope_head_dim`` (``qk_rope_head_dim``) and
``value_head_dim`` (``v_head_dim``), one leading dense layer, the sigmoid router
with ``routed_scale``, and ``shared_expert_dim``: a dense SwiGLU beside the
routed share of every sparse layer (replay_tpu.nn.blocks).

And the looped stack of the public ``ouro`` configuration
(https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json, ``total_ut_steps``):
``loop_steps`` T applies the SAME stack T times, the final norm closing every
step and its output carried into the next, and after each step an exit gate (a
``Dense(d -> 1)`` with bias, in float32, named ``exit_gate``) scores how likely
the model is to stop there; with it ``sandwich_norms`` (an RMSNorm after each
sublayer too) and ``qk_norm=False``:

    h0 = table[items] * keep
    for t in 1..T:  h_t = rms(LayerPatternEncoder(h_{t-1}));  g_t = w . h_t + b
    lambda_t = sigmoid(g_t);  p(t) = lambda_t prod_{j<t} (1 - lambda_j),  p(T) = prod_{j<T} (1 - lambda_j)

The steps are ONE ``nn.scan`` body over the broadcast parameters (the paths stay
``encoder/...``, ``final_norm``, ``exit_gate``), under the scopes ``recurrence``
(the stack) and ``exit_gate`` (final norm and gate). The training forward returns
h_T, as inference and predict read it; it also sows ``exits``
(``hidden`` [T, B, L, d], ``gate_logits`` [T, B, L] in float32) for the loss that
weights a loss at every exit (replay_tpu.nn.loss.ExitWeightedCE; ``Trainer``
binds them), and ``loop_layer_applications`` into ``counters``: the blocks the
scan applied, a step. ``remat`` recomputes each block application from its input
on the way back (``Trainer(remat_policy=...)``). ``loop_steps`` 1 (the default)
is the one-pass model: no scan, no gate, its program and parameter paths.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from replay_tpu.data.nn.schema import TensorMap, TensorSchema
from replay_tpu.nn.attention import RMSNorm
from replay_tpu.nn.blocks import LayerPatternEncoder, needs_mask
from replay_tpu.nn.embedding import SequenceEmbedding
from replay_tpu.nn.head import EmbeddingTyingHead
from replay_tpu.nn.mask import causal_attention_mask
from replay_tpu.parallel.sharding import shard_activation


class HybridRec(nn.Module):
    """The layer-pattern next-item model with an embedding-tying head, or with
    an output table of its own (``tie_embeddings=False``; see the module docstring). The item feature's ``embedding_dim`` in the schema is the
    model width. ``experts_held`` / ``expert_offset``: the share of each expert
    layer that lives on this chip (``None``: every expert); what the expert
    layers count (``expert_load``, ``dropped_assignments``) rides the trainer's
    step metrics."""

    logits_via_item_weights = True  # bias-free head: get_logits(h) == h . table^T
    sows_counters = True  # the expert layers sow into the `counters` collection

    schema: TensorSchema
    layer_types: Sequence[str] = ("conv", "full_attention")
    num_dense_layers: int = 1
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: Optional[int] = None  # None: width / num_heads
    rope_theta: float = 1_000_000.0
    conv_kernel: int = 3
    dense_dim: int = 256
    expert_dim: int = 64
    num_experts: int = 8
    experts_held: Optional[int] = None
    expert_offset: int = 0
    experts_per_token: int = 2
    routed_scale: float = 1.0
    norm_eps: float = 1e-5
    router: str = "sigmoid"
    sliding_window: Optional[int] = None
    fused_attention: bool = False
    rope_scaling: Optional[Mapping[str, Any]] = None  # {layer type: rope_parameters}
    kv_latent_dim: Optional[int] = None  # latent_attention layers: see replay_tpu.nn.blocks
    rope_head_dim: Optional[int] = None
    value_head_dim: Optional[int] = None
    shared_expert_dim: int = 0  # 0: no shared expert beside the routed ones
    tie_embeddings: bool = True
    loop_steps: int = 1  # T: passes of the whole stack over one set of weights, each gated
    sandwich_norms: bool = False  # an RMSNorm after each sublayer too (replay_tpu.nn.blocks)
    qk_norm: bool = True
    remat: bool = False  # one checkpoint per block application (Trainer(remat_policy=...))
    remat_policy: Any = None
    excluded_features: tuple = ()
    dtype: Any = jnp.float32
    embedding_init: Any = None

    @property
    def sows_exits(self) -> bool:
        """Whether the training forward sows ``exits`` for the loss (it loops)."""
        return self.loop_steps > 1

    def setup(self) -> None:
        self.embedder = SequenceEmbedding(
            schema=self.schema, excluded_features=self.excluded_features,
            dtype=self.dtype, embedding_init=self.embedding_init, name="embedder",
        )
        width = self.schema[self.schema.item_id_feature_name].embedding_dim
        self.encoder = LayerPatternEncoder(
            layer_types=tuple(self.layer_types), num_dense_layers=self.num_dense_layers,
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim or width // self.num_heads,
            rope_theta=self.rope_theta, conv_kernel=self.conv_kernel,
            dense_dim=self.dense_dim, expert_dim=self.expert_dim,
            num_experts=self.num_experts, experts_held=self.experts_held,
            expert_offset=self.expert_offset, experts_per_token=self.experts_per_token,
            routed_scale=self.routed_scale, norm_eps=self.norm_eps,
            dtype=self.dtype, router=self.router, sliding_window=self.sliding_window,
            fused_attention=self.fused_attention, rope_scaling=self.rope_scaling,
            kv_latent_dim=self.kv_latent_dim, rope_head_dim=self.rope_head_dim,
            value_head_dim=self.value_head_dim, shared_expert_dim=self.shared_expert_dim,
            sandwich=self.sandwich_norms, qk_norm=self.qk_norm, remat=self.remat,
            remat_policy=self.remat_policy, name="encoder",
        )
        self.final_norm = RMSNorm(self.norm_eps, dtype=self.dtype, name="final_norm")
        if self.loop_steps > 1:
            self.gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate")
        self.head = EmbeddingTyingHead()
        if not self.tie_embeddings:
            items = self.schema[self.schema.item_id_feature_name].cardinality
            init = self.embedding_init or nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", out_axis=0
            )
            self.output_table = self.param("output_table", init, (items, width))

    def __call__(self, feature_tensors: TensorMap, padding_mask: jnp.ndarray) -> jnp.ndarray:
        """Hidden states [B, L, E] (the training forward)."""
        with jax.named_scope("embed"):
            embeddings = self.embedder(feature_tensors)
            x = sum(embeddings[name] for name in sorted(embeddings))
            x = x * padding_mask[..., None].astype(x.dtype)
            x = shard_activation(x, "batch", "length", "embed")
        with jax.named_scope("encoder"):
            # the fused route builds its mask in-kernel: nothing [L, L] unless a layer needs it
            mask = None
            if needs_mask(self.layer_types, self.fused_attention):
                mask = causal_attention_mask(padding_mask, dtype=self.dtype)
            if self.loop_steps > 1:
                return self._loop(x, mask, padding_mask)
            x = self.encoder(x, mask, padding_mask)
        with jax.named_scope("final_norm"):
            return shard_activation(self.final_norm(x), "batch", "length", "embed")

    def _loop(self, x, mask, padding_mask):
        """``loop_steps`` passes as ONE scanned body (module docstring)."""

        def one_step(model, carry, _):
            h, applied = carry
            with jax.named_scope("recurrence"):
                u = model.encoder(h, mask, padding_mask)
            with jax.named_scope("exit_gate"):
                out = shard_activation(model.final_norm(u), "batch", "length", "embed")
                gate = model.gate(out.astype(jnp.float32))[..., 0]
            return (out, applied + len(model.layer_types)), (out, gate)

        steps = nn.scan(
            one_step, variable_broadcast="params", split_rngs={"params": False},
            variable_axes={"counters": 0}, length=self.loop_steps,
        )
        (last, applied), (hidden, gate_logits) = steps(self, (x, jnp.int32(0)), None)
        latest = {"reduce_fn": lambda _, new: new, "init_fn": lambda: None}  # one value a step
        self.sow("counters", "loop_layer_applications", applied, **latest)
        self.sow("exits", "hidden", hidden, **latest)
        self.sow("exits", "gate_logits", gate_logits, **latest)
        return last

    def get_logits(
        self, hidden: jnp.ndarray, candidates_to_score: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        """Scores against the catalog, or against candidate ids ([K] or [B, ..., K])."""
        if candidates_to_score is None:
            return self.head(hidden, self.get_item_weights())
        if self.tie_embeddings:
            embedded = self.embedder.get_item_weights(candidates_to_score)
        else:
            embedded = jnp.take(self.output_table, candidates_to_score, axis=0)
        if candidates_to_score.ndim == 1:
            return self.head(hidden, embedded)
        return jnp.einsum("...e,...ke->...k", hidden, embedded)

    def forward_inference(
        self,
        feature_tensors: TensorMap,
        padding_mask: jnp.ndarray,
        candidates_to_score: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Scores of the NEXT item after each sequence: [B, num_items] or [B, K]."""
        return self.get_logits(self(feature_tensors, padding_mask)[:, -1, :], candidates_to_score)

    def get_query_embeddings(
        self, feature_tensors: TensorMap, padding_mask: jnp.ndarray
    ) -> jnp.ndarray:
        return self(feature_tensors, padding_mask)[:, -1, :]

    def get_item_weights(self) -> jnp.ndarray:
        """The table the head scores against: the item table, or the untied one."""
        if self.tie_embeddings:
            return self.embedder.get_item_weights()
        return self.output_table  # float32 like the item table: the product promotes
