"""BERT4Rec: bidirectional masked-LM next-item model.

Capability parity with replay/models/nn/sequential/bert4rec/model.py:10-425
(BertEmbedding = item + positional embeddings with LayerNorm/dropout, N transformer
blocks with ``num_passes_over_block``, tying or classification head) and its MLM
datasets (dataset.py:55,95,264 — uniform masking for training, mask-token append
for next-item inference).

TPU design differences from the reference:
* the ``<MASK>`` token is a learned vector substituted into the summed feature
  embedding BEFORE positions are added — no vocabulary surgery, the item table
  keeps its ``cardinality+1`` rows and weight tying stays aligned;
* inference appends the mask token by shifting the (left-padded) sequence one
  slot left and masking the last position — a static-shape roll, jit-safe;
* attention is the padding-only bidirectional mask (replay_tpu/nn/mask.py).

Training batches carry ``token_mask`` (True = visible) from TokenMaskTransform;
targets are the original ids at masked positions (see
make_default_bert4rec_transforms).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from replay_tpu.data.nn.schema import TensorMap, TensorSchema
from replay_tpu.nn.embedding import SequenceEmbedding
from replay_tpu.nn.head import EmbeddingTyingHead
from replay_tpu.nn.mask import attention_mask_for_route
from replay_tpu.obs.health import sow_stage_stats
from replay_tpu.parallel.sharding import shard_activation

from ..sasrec.transformer import SasRecTransformerLayer


class Bert4RecBody(nn.Module):
    """Embed → mask-substitute → +position → LN/dropout → bidirectional encoder."""

    schema: TensorSchema
    embedding_dim: int = 64
    num_blocks: int = 2
    num_heads: int = 4
    max_sequence_length: int = 50
    hidden_dim: Optional[int] = None
    dropout_rate: float = 0.0
    activation: str = "gelu"
    num_passes_over_block: int = 1
    remat: bool = False
    remat_policy: Any = None  # jax.checkpoint policy (Trainer(remat_policy=...))
    scan_blocks: bool = False  # nn.scan over the block stack ([layers, ...] params)
    use_flash: Any = False  # False | True | "tiled" (long L) | "ring" (seq-parallel)
    excluded_features: tuple = ()
    dtype: Any = jnp.float32

    def setup(self) -> None:
        self.embedder = SequenceEmbedding(
            schema=self.schema,
            excluded_features=self.excluded_features,
            dtype=self.dtype,
            name="embedder",
        )
        self.mask_embedding = self.param(
            "mask_embedding", nn.initializers.normal(stddev=0.02), (self.embedding_dim,)
        )
        self.positional_embedding = self.param(
            "positional_embedding",
            nn.initializers.normal(stddev=0.02),
            (self.max_sequence_length, self.embedding_dim),
        )
        self.input_norm = nn.LayerNorm(dtype=self.dtype, name="input_norm")
        self.input_dropout = nn.Dropout(self.dropout_rate)
        self.encoder = SasRecTransformerLayer(
            num_blocks=self.num_blocks,
            num_heads=self.num_heads,
            hidden_dim=self.hidden_dim or self.embedding_dim * 4,
            dropout_rate=self.dropout_rate,
            activation=self.activation,
            remat=self.remat,
            remat_policy=self.remat_policy,
            scan_blocks=self.scan_blocks,
            use_flash=self.use_flash,
            dtype=self.dtype,
            name="encoder",
        )
        self.final_norm = nn.LayerNorm(dtype=self.dtype, name="final_norm")

    def __call__(
        self,
        feature_tensors: TensorMap,
        padding_mask: jnp.ndarray,  # [B, L] bool
        token_mask: Optional[jnp.ndarray] = None,  # [B, L] (or [B, L, 1]) bool, True=visible
        deterministic: bool = True,
        segment_ids: Optional[jnp.ndarray] = None,  # [B, L] int, packed batches
    ) -> jnp.ndarray:
        # named scopes label the HLO per stage, as SasRecBody's do: a device
        # profile splits the forward into embed / encoder / final_norm
        with jax.named_scope("embed"):
            embeddings = self.embedder(feature_tensors)
            total = sum(embeddings[name] for name in sorted(embeddings))
            if token_mask is not None:
                visible = token_mask.reshape(token_mask.shape[0], token_mask.shape[1])
                total = jnp.where(
                    visible[..., None], total, self.mask_embedding.astype(total.dtype)
                )
            seq_len = total.shape[1]
            if seq_len > self.max_sequence_length:
                msg = (
                    f"Sequence length {seq_len} exceeds positional table size "
                    f"{self.max_sequence_length}"
                )
                raise ValueError(msg)
            # left-padded inputs: the most recent position maps to the last table row
            x = total + self.positional_embedding[
                self.max_sequence_length - seq_len :
            ].astype(total.dtype)
            x = self.input_dropout(self.input_norm(x), deterministic=deterministic)
            # rule-table activation constraint: [B, L, E] pinned to the (batch,
            # length, embed) rules under the trainer's sharding scope (the SP
            # layout between ring-attention blocks); a no-op outside any scope
            x = shard_activation(x, "batch", "length", "embed")
            # model-health stage stats (no-op unless `intermediates` is mutable)
            sow_stage_stats(self, "embed", x)
        with jax.named_scope("encoder"):
            # packed rows (segment_ids) get the block-diagonal bidirectional
            # mask: attention never crosses a packed segment boundary
            attention_mask = attention_mask_for_route(
                self.use_flash, padding_mask, causal=False,
                deterministic=deterministic, dtype=self.dtype,
                segment_ids=segment_ids,
            )
            for _ in range(self.num_passes_over_block):
                x = self.encoder(
                    x, attention_mask, padding_mask,
                    deterministic=deterministic, causal=False,
                )
        with jax.named_scope("final_norm"):
            out = self.final_norm(x)
            out = shard_activation(out, "batch", "length", "embed")
            sow_stage_stats(self, "final_norm", out)
            return out


class Bert4Rec(nn.Module):
    """BERT4Rec with an embedding-tying head."""

    # bias-free head contract: get_logits(h) == h . get_item_weights()^T
    logits_via_item_weights = True

    schema: TensorSchema
    embedding_dim: int = 64
    num_blocks: int = 2
    num_heads: int = 4
    max_sequence_length: int = 50
    hidden_dim: Optional[int] = None
    dropout_rate: float = 0.0
    activation: str = "gelu"
    num_passes_over_block: int = 1
    remat: bool = False
    remat_policy: Any = None  # jax.checkpoint policy (Trainer(remat_policy=...))
    scan_blocks: bool = False  # nn.scan over the block stack ([layers, ...] params)
    use_flash: Any = False  # False | True | "tiled" (long L) | "ring" (seq-parallel)
    excluded_features: tuple = ()
    dtype: Any = jnp.float32

    @classmethod
    def from_params(
        cls,
        schema: TensorSchema,
        embedding_dim: int = 192,
        num_heads: int = 4,
        num_blocks: int = 2,
        max_sequence_length: int = 50,
        dropout: float = 0.3,
        excluded_features=None,
        **kwargs,
    ) -> "Bert4Rec":
        """Keyword-compatible constructor matching the SasRec/TwoTower shape
        (the reference's legacy bert4rec spells these block_count/head_count/
        hidden_size — see docs/migration_from_replay.md)."""
        excluded = {
            name
            for name in (schema.query_id_feature_name, schema.timestamp_feature_name)
            if name is not None
        } | set(excluded_features or [])
        return cls(
            schema=schema,
            embedding_dim=embedding_dim,
            num_heads=num_heads,
            num_blocks=num_blocks,
            max_sequence_length=max_sequence_length,
            dropout_rate=dropout,
            excluded_features=tuple(sorted(excluded)),
            **kwargs,
        )

    def setup(self) -> None:
        self.body = Bert4RecBody(
            schema=self.schema,
            embedding_dim=self.embedding_dim,
            num_blocks=self.num_blocks,
            num_heads=self.num_heads,
            max_sequence_length=self.max_sequence_length,
            hidden_dim=self.hidden_dim,
            dropout_rate=self.dropout_rate,
            activation=self.activation,
            num_passes_over_block=self.num_passes_over_block,
            remat=self.remat,
            remat_policy=self.remat_policy,
            scan_blocks=self.scan_blocks,
            use_flash=self.use_flash,
            excluded_features=self.excluded_features,
            dtype=self.dtype,
            name="body",
        )
        self.head = EmbeddingTyingHead()

    def __call__(
        self,
        feature_tensors: TensorMap,
        padding_mask: jnp.ndarray,
        token_mask: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
        segment_ids: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Hidden states [B, L, E]; masked positions are the MLM prediction sites.
        ``segment_ids`` (packed batches) makes attention block-diagonal."""
        return self.body(
            feature_tensors, padding_mask, token_mask=token_mask,
            deterministic=deterministic, segment_ids=segment_ids,
        )

    def get_logits(
        self, hidden: jnp.ndarray, candidates_to_score: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        """Score hidden states against the catalog (or candidate ids)."""
        if candidates_to_score is None:
            return self.head(hidden, self.body.embedder.get_item_weights())
        embedded = self.body.embedder.get_item_weights(candidates_to_score)
        if candidates_to_score.ndim == 1:
            return self.head(hidden, embedded)
        return jnp.einsum("...e,...ke->...k", hidden, embedded)

    def forward_inference(
        self,
        feature_tensors: TensorMap,
        padding_mask: jnp.ndarray,
        candidates_to_score: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Next-item scores: append ``<MASK>`` after the last event and read its
        logits (ref bert4rec/dataset.py:264 — prediction dataset appends the mask
        token; here it's a static-shape left-shift)."""
        shifted_features = {
            name: jnp.concatenate([value[:, 1:], value[:, -1:]], axis=1)
            if value.ndim >= 2
            else value
            for name, value in feature_tensors.items()
        }
        shifted_padding = jnp.concatenate(
            [padding_mask[:, 1:], jnp.ones_like(padding_mask[:, -1:])], axis=1
        )
        # only the appended slot is masked
        token_mask = jnp.concatenate(
            [
                jnp.ones_like(shifted_padding[:, :-1]),
                jnp.zeros_like(shifted_padding[:, -1:]),
            ],
            axis=1,
        )
        hidden = self.body(
            shifted_features, shifted_padding, token_mask=token_mask, deterministic=True
        )
        return self.get_logits(hidden[:, -1, :], candidates_to_score)

    def get_item_weights(self) -> jnp.ndarray:
        """Item-embedding table [num_items, E] (the SCE loss's negatives pool)."""
        return self.body.embedder.get_item_weights()

    def get_query_embeddings(
        self, feature_tensors: TensorMap, padding_mask: jnp.ndarray
    ) -> jnp.ndarray:
        """Mask-position hidden state per query [B, E]."""
        shifted = {
            name: jnp.concatenate([value[:, 1:], value[:, -1:]], axis=1)
            if value.ndim >= 2
            else value
            for name, value in feature_tensors.items()
        }
        shifted_padding = jnp.concatenate(
            [padding_mask[:, 1:], jnp.ones_like(padding_mask[:, -1:])], axis=1
        )
        token_mask = jnp.concatenate(
            [jnp.ones_like(shifted_padding[:, :-1]), jnp.zeros_like(shifted_padding[:, -1:])],
            axis=1,
        )
        return self.body(shifted, shifted_padding, token_mask=token_mask, deterministic=True)[
            :, -1, :
        ]
