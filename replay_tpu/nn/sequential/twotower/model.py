"""TwoTower retrieval model: transformer query tower × feature-fused item tower.

Capability parity with replay/nn/sequential/twotower/model.py:53-529 (QueryTower
transformer over the interaction sequence, ItemTower encoding the whole catalog
through a SwiGLU encoder with id + item-feature fusion, shared embedding tables
between the towers, ``from_item_features`` construction from an encoded
item-features frame) and reader.py:18 (FeaturesReader →
replay_tpu.nn.sequential.twotower.reader).

TPU design — functional catalog instead of persistent buffers:
* the reference stores every catalog feature as a registered torch buffer
  (``item_reference_*``) and caches eval-time catalog embeddings inside the
  module, invalidating on train. Here catalog features are plain INPUTS
  (``item_feature_tensors``: dict of [num_items, ...] arrays) — they ride into
  jit as constants-by-sharding, can be sharded over the mesh like any other
  array, and "cache invalidation" is just recomputing ``encode_items`` after a
  train step (the Trainer's validate/predict call it per evaluation pass).
* both towers share ONE item-id embedding table (weight tying with the catalog),
  so the logits are a [B, E] × [E, I] matmul on the MXU.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from replay_tpu.data.nn.schema import TensorMap, TensorSchema
from replay_tpu.nn.agg import PositionAwareAggregator
from replay_tpu.nn.embedding import SequenceEmbedding
from replay_tpu.nn.ffn import SwiGLUEncoder
from replay_tpu.nn.head import EmbeddingTyingHead
from replay_tpu.nn.mask import attention_mask_for_route

from ..sasrec.transformer import SasRecTransformerLayer


class TwoTower(nn.Module):
    """Query tower (sequence transformer) scored against the item tower.

    :param schema: query-side sequential features (must contain ITEM_ID).
    :param item_schema: optional non-sequential item-side features fused into the
        item tower; their tensors arrive at call time as ``item_feature_tensors``
        (see :class:`~replay_tpu.nn.sequential.twotower.reader.FeaturesReader`).
    :param context_merger: optional flax module fusing the query tower's hidden
        states with the raw input features — called as
        ``merger(hidden [B, L, E], feature_tensors) -> [B, L, E]`` after the
        final norm, in both training and inference (ref ContextMergerProto,
        replay/nn/sequential/twotower/model.py:421,516,667-672,704-710).
    """

    schema: TensorSchema
    item_schema: Optional[TensorSchema] = None
    context_merger: Optional[nn.Module] = None
    embedding_dim: int = 64
    num_blocks: int = 2
    num_heads: int = 1
    max_sequence_length: int = 50
    hidden_dim: Optional[int] = None
    dropout_rate: float = 0.0
    item_encoder_blocks: int = 1
    excluded_features: tuple = ()
    use_flash: Any = False  # False | True | "tiled" (long L, mask-free)
    dtype: Any = jnp.float32

    @classmethod
    def from_params(
        cls,
        schema: TensorSchema,
        item_schema: Optional[TensorSchema] = None,
        embedding_dim: int = 192,
        num_heads: int = 4,
        num_blocks: int = 2,
        max_sequence_length: int = 50,
        dropout: float = 0.3,
        excluded_features=None,
        **kwargs,
    ) -> "TwoTower":
        """The reference's keyword-compatible constructor (twotower/model.py:536).
        The reference's ``item_features_reader`` becomes ``item_schema`` + call-time
        ``item_feature_tensors`` (see FeaturesReader)."""
        excluded = {
            name
            for name in (schema.query_id_feature_name, schema.timestamp_feature_name)
            if name is not None
        } | set(excluded_features or [])
        return cls(
            schema=schema,
            item_schema=item_schema,
            embedding_dim=embedding_dim,
            num_heads=num_heads,
            num_blocks=num_blocks,
            max_sequence_length=max_sequence_length,
            dropout_rate=dropout,
            excluded_features=tuple(sorted(excluded)),
            **kwargs,
        )

    def setup(self) -> None:
        self.embedder = SequenceEmbedding(
            schema=self.schema,
            excluded_features=self.excluded_features,
            dtype=self.dtype,
            name="embedder",
        )
        self.aggregator = PositionAwareAggregator(
            embedding_dim=self.embedding_dim,
            max_sequence_length=self.max_sequence_length,
            dropout_rate=self.dropout_rate,
            dtype=self.dtype,
            name="aggregator",
        )
        self.encoder = SasRecTransformerLayer(
            num_blocks=self.num_blocks,
            num_heads=self.num_heads,
            hidden_dim=self.hidden_dim or self.embedding_dim * 4,
            dropout_rate=self.dropout_rate,
            use_flash=self.use_flash,
            dtype=self.dtype,
            name="encoder",
        )
        self.final_norm = nn.LayerNorm(dtype=self.dtype, name="final_norm")
        if self.item_schema is not None:
            self.item_feature_embedder = SequenceEmbedding(
                schema=self.item_schema, dtype=self.dtype, name="item_feature_embedder"
            )
        self.item_encoder = SwiGLUEncoder(
            num_blocks=self.item_encoder_blocks,
            hidden_dim=self.hidden_dim or self.embedding_dim * 4,
            output_dim=self.embedding_dim,
            dropout_rate=self.dropout_rate,
            dtype=self.dtype,
            name="item_encoder",
        )
        self.head = EmbeddingTyingHead()

    # -- query tower -------------------------------------------------------- #
    def __call__(
        self,
        feature_tensors: TensorMap,
        padding_mask: jnp.ndarray,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        """Query hidden states [B, L, E]."""
        # named scopes label the HLO per stage, as SasRecBody's do
        with jax.named_scope("embed"):
            embeddings = self.embedder(feature_tensors)
            x = self.aggregator(embeddings, deterministic=deterministic)
        with jax.named_scope("encoder"):
            attention_mask = attention_mask_for_route(
                self.use_flash, padding_mask, causal=True,
                deterministic=deterministic, dtype=self.dtype,
            )
            x = self.encoder(x, attention_mask, padding_mask, deterministic=deterministic)
        with jax.named_scope("final_norm"):
            x = self.final_norm(x)
        if self.context_merger is not None:
            x = self.context_merger(x, feature_tensors)
        return x

    # -- item tower --------------------------------------------------------- #
    def encode_items(
        self,
        candidates: Optional[jnp.ndarray] = None,
        item_feature_tensors: Optional[TensorMap] = None,
    ) -> jnp.ndarray:
        """Item-tower embeddings: [num_items, E] for the catalog, or the rows of
        ``candidates`` ([..., E]) — id embedding + fused item features through the
        SwiGLU encoder."""
        base = self.embedder.get_item_weights(candidates)
        if self.item_schema is not None and item_feature_tensors is not None:
            feature_tensors = item_feature_tensors
            if candidates is not None:
                feature_tensors = {
                    name: value[candidates] for name, value in item_feature_tensors.items()
                }
            fused = self.item_feature_embedder(feature_tensors)
            for name in sorted(fused):
                base = base + fused[name]
        return self.item_encoder(base)

    # -- scoring ------------------------------------------------------------ #
    def get_logits(
        self,
        hidden: jnp.ndarray,
        candidates_to_score: Optional[jnp.ndarray] = None,
        item_feature_tensors: Optional[TensorMap] = None,
    ) -> jnp.ndarray:
        items = self.encode_items(candidates_to_score, item_feature_tensors)
        if candidates_to_score is None or candidates_to_score.ndim == 1:
            return self.head(hidden, items)
        return jnp.einsum("...e,...ke->...k", hidden, items)

    def forward_inference(
        self,
        feature_tensors: TensorMap,
        padding_mask: jnp.ndarray,
        candidates_to_score: Optional[jnp.ndarray] = None,
        item_feature_tensors: Optional[TensorMap] = None,
    ) -> jnp.ndarray:
        """Retrieval scores of the next item: [B, num_items] or [B, K]."""
        hidden = self(feature_tensors, padding_mask, deterministic=True)
        return self.get_logits(hidden[:, -1, :], candidates_to_score, item_feature_tensors)

    def get_query_embeddings(
        self, feature_tensors: TensorMap, padding_mask: jnp.ndarray
    ) -> jnp.ndarray:
        return self(feature_tensors, padding_mask, deterministic=True)[:, -1, :]
