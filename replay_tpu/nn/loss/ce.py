"""Cross-entropy losses: full-catalog and negative-sampled variants.

Capability parity with replay/nn/loss/ce.py:10-340 (CE, CEWeighted, CESampled,
CESampledWeighted).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .base import LossBase, broadcast_negatives, mask_negative_logits, masked_mean


@jax.custom_vjp
def _softmax_nll(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-row ``-log_softmax(logits)[label]`` whose backward keeps no second ``[..., I]`` tensor.

    JAX's rule for ``log_softmax`` keeps a tensor made inside it for the way back
    (``exp(logits - max)`` as traced, ``logits - max - log_sum`` as the TPU compiler
    stores it): a second tensor of the logits' shape that is one scalar a row away
    from them. This saves the logits, the per-row log-sum-exp and the labels instead,
    and forms ``g * (exp(logits - lse) - onehot(label))`` as one elementwise
    expression for XLA to fuse into the two products that consume it.
    """
    return _softmax_nll_fwd(logits, labels)[0]


def _softmax_nll_fwd(logits, labels):
    # log_softmax + take_along_axis, in their own order of operations
    row_max = jnp.max(logits, axis=-1, keepdims=True)
    log_sum = jnp.log(jnp.sum(jnp.exp(logits - row_max), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    nll = (log_sum - (picked - row_max))[..., 0]
    return nll, (logits, row_max + log_sum, labels)


def _softmax_nll_bwd(residuals, g):
    logits, lse, labels = residuals
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    dlogits = g[..., None] * (jnp.exp(logits - lse) - onehot)
    return dlogits, None


_softmax_nll.defvjp(_softmax_nll_fwd, _softmax_nll_bwd)


class CE(LossBase):
    """Full-softmax cross-entropy over the whole item catalog.

    For the way back the head saves the logits as ``get_logits`` produced them and
    one log-sum-exp a row (:func:`_softmax_nll`), not the second tensor of the logits'
    shape that ``log_softmax``'s own rule keeps: at 25,600 positions by 27,278 items
    that is 2.8 GB of float32 written and held across the step for the sake of one
    scalar a row.
    """

    def __call__(
        self,
        model_embeddings,
        feature_tensors,
        positive_labels,
        negative_labels,
        padding_mask,
        target_padding_mask,
    ) -> jnp.ndarray:
        if positive_labels.shape[-1] != 1:
            msg = "Multi-positive labels are not supported by the CE loss"
            raise NotImplementedError(msg)
        logits = self.logits_callback(model_embeddings)  # [B, L, I]
        labels = jnp.clip(positive_labels[..., 0], 0, logits.shape[-1] - 1)
        nll = _softmax_nll(logits, labels)
        weights = self._label_weights(labels, nll.dtype)
        mask = target_padding_mask[..., 0].astype(nll.dtype) * weights
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def _label_weights(self, labels, dtype):
        return jnp.ones_like(labels, dtype=dtype)


class CEFused(CE):
    """CE with the pallas fused-logsumexp head (TPU).

    Bitwise-equivalent math to :class:`CE` up to f32-vs-bf16 softmax precision
    (the fused path accumulates in f32 inside VMEM), but the ``[B, L, I]``
    logits tensor never reaches HBM — the dominant train-step traffic at
    full-catalog scales. Compiled on the ``"tpu"`` backend; on ``"cpu"`` the
    Pallas interpreter stands in (logged once at WARNING); any other backend
    raises unless ``interpret=`` is given (``ops.flash_attention.pallas_interpret``).

    Contract: the loss reconstructs logits as ``hidden · get_item_weights()ᵀ``,
    so it matches :class:`CE` only for models whose ``get_logits`` is a
    BIAS-FREE tying head over that same table (SasRec/TiSasRec/Bert4Rec). Such
    models declare ``logits_via_item_weights = True``; the trainer refuses to
    bind CEFused to a model without that declaration (a model adding an item
    bias or scale would otherwise silently train with a different loss).
    """

    needs_item_embeddings = True
    requires_tying_head = True
    # the full [B, L, I] logits never exist on this path: health's logits-stats
    # collector must stream its last-position stats over catalog chunks (or
    # flag itself skipped) instead of calling get_logits (obs.health)
    avoid_full_logits = True

    def __init__(
        self, tile: int = 256, item_tile: Optional[int] = None, interpret: bool = None
    ) -> None:
        super().__init__()
        self.tile = tile
        self.item_tile = item_tile
        self.interpret = interpret
        self.item_embeddings_callback = None

    def _item_table(self) -> jnp.ndarray:
        if self.item_embeddings_callback is None:
            msg = (
                f"{type(self).__name__} reconstructs logits from the raw item "
                "table, but no item_embeddings_callback is bound. Train through "
                "replay_tpu.nn.Trainer, which binds the model's "
                "get_item_weights() automatically — a model that defines no "
                "get_item_weights cannot drive this loss at all — or, for "
                "direct use, set loss.item_embeddings_callback to a zero-arg "
                "callable returning the [num_items, embed] table."
            )
            raise AttributeError(msg)
        return self.item_embeddings_callback()

    def _check_dtypes(self, hidden: jnp.ndarray, table: jnp.ndarray) -> None:
        """Reject dtype mismatches the kernel would silently paper over.

        Sanctioned: identical dtypes, and the flax compute-dtype split where
        one side is the float32 PARAM table (or f32 hidden) and the other a
        narrower float — the kernel accumulates in f32, exactly what
        ``get_logits``'s einsum promotion does. This is the precision
        ladder's bf16 rung (``Trainer(precision="bf16")``: bf16 hidden
        states against the f32 master table, docs/performance.md "The
        precision ladder"). Anything else (an integer / quantized table, two
        different narrow floats) is a bug at the call site, named here
        instead of surfacing as a wrong-loss training run.
        """
        h_dt, t_dt = jnp.dtype(hidden.dtype), jnp.dtype(table.dtype)
        floats = jnp.issubdtype(h_dt, jnp.floating) and jnp.issubdtype(t_dt, jnp.floating)
        sanctioned = h_dt == t_dt or (
            floats and jnp.dtype(jnp.float32) in (h_dt, t_dt)
        )
        if not sanctioned:
            msg = (
                f"{type(self).__name__}: hidden states are {h_dt} but the item "
                f"table is {t_dt}. Only matching dtypes, or the sanctioned "
                "mixed-precision split — narrow-float compute (e.g. bfloat16 "
                "hidden states, the Trainer(precision='bf16') rung) against "
                "the float32 master/param table, accumulated in f32 inside "
                "the kernel — are supported; cast the model or the table "
                "explicitly. int8 tables belong to the SERVING ladder rung "
                "(replay_tpu.serve.quant + MIPSIndex), never to training."
            )
            raise ValueError(msg)

    def _resolve_interpret(self) -> bool:
        from replay_tpu.ops import pallas_interpret

        return pallas_interpret() if self.interpret is None else self.interpret

    def _lse(self, hidden2d: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
        """``[N]`` catalog logsumexp — the seam :class:`CEFusedTP` overrides."""
        from replay_tpu.ops.fused_ce import fused_lse

        return fused_lse(hidden2d, table, self.tile, self.item_tile, self._resolve_interpret())

    def __call__(
        self,
        model_embeddings,
        feature_tensors,
        positive_labels,
        negative_labels,
        padding_mask,
        target_padding_mask,
    ) -> jnp.ndarray:
        if positive_labels.shape[-1] != 1:
            msg = "Multi-positive labels are not supported by the CE loss"
            raise NotImplementedError(msg)
        table = self._item_table()  # [I, E]
        self._check_dtypes(model_embeddings, table)
        num_items = table.shape[0]
        hidden = model_embeddings.reshape(-1, model_embeddings.shape[-1])
        labels = jnp.clip(positive_labels[..., 0], 0, num_items - 1)
        lse = self._lse(hidden, table).reshape(labels.shape)
        label_logit = jnp.sum(
            model_embeddings.astype(jnp.float32) * table[labels].astype(jnp.float32),
            axis=-1,
        )
        nll = lse - label_logit
        weights = self._label_weights(labels, nll.dtype)
        mask = target_padding_mask[..., 0].astype(nll.dtype) * weights
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


class CEFusedTP(CEFused):
    """:class:`CEFused` with the item table sharded over the mesh's TP axis.

    The catalog lives ``[I/n_tp, E]`` per device (the layout
    ``Trainer(shard_vocab=True)`` already places the embedding params in);
    each shard runs the tile-wise online logsumexp locally and the shards
    combine with a two-pass psum-style reduction inside ``shard_map``
    (:func:`replay_tpu.parallel.sharded_fused_lse`). Backward: ``dh`` is
    psummed across catalog shards, ``dW`` stays shard-local — the table is
    never gathered to one device, which is what lets the catalog scale past
    single-device HBM (ROADMAP item 1's million-item north star).

    The trainer binds :attr:`mesh` automatically (``needs_mesh``); direct
    callers assign it before the first call. ``axis_name``/``data_axis``
    default to the trainer mesh's ``("data", "model")`` axes.
    """

    needs_mesh = True

    def __init__(
        self,
        tile: int = 256,
        item_tile: Optional[int] = None,
        interpret: bool = None,
        axis_name: str = "model",
        data_axis: Optional[str] = "data",
    ) -> None:
        super().__init__(tile, item_tile, interpret)
        self.axis_name = axis_name
        self.data_axis = data_axis
        self.mesh = None

    def _lse(self, hidden2d: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
        from replay_tpu.parallel.sharded_ce import sharded_fused_lse

        if self.mesh is None:
            msg = (
                "CEFusedTP needs the device mesh to shard the catalog over: "
                "train through replay_tpu.nn.Trainer (which binds loss.mesh) "
                "or assign loss.mesh before the first call."
            )
            raise AttributeError(msg)
        return sharded_fused_lse(
            hidden2d,
            table,
            self.mesh,
            axis_name=self.axis_name,
            data_axis=self.data_axis,
            tile=self.tile,
            item_tile=self.item_tile,
            interpret=self._resolve_interpret(),
        )


class CEWeighted(CE):
    """CE with per-class weights (reference: torch CrossEntropyLoss(weight=...))."""

    def __init__(self, weight) -> None:
        super().__init__()
        self.weight = jnp.asarray(weight)

    def _label_weights(self, labels, dtype):
        return self.weight[labels].astype(dtype)


class CESampled(LossBase):
    """Softmax CE between each positive and K sampled negatives.

    Supports multi-positive labels and all three negative shapes; negatives equal to
    ``negative_labels_ignore_index`` are excluded from the softmax.
    """

    def __init__(self, negative_labels_ignore_index: int = -100) -> None:
        super().__init__()
        self.negative_labels_ignore_index = negative_labels_ignore_index

    def __call__(
        self,
        model_embeddings,
        feature_tensors,
        positive_labels,
        negative_labels,
        padding_mask,
        target_padding_mask,
    ) -> jnp.ndarray:
        batch, length, num_pos = positive_labels.shape
        negatives = broadcast_negatives(negative_labels, batch, length)  # [B, L, N]

        safe_neg = jnp.where(negatives == self.negative_labels_ignore_index, 0, negatives)
        negative_logits = self.logits_callback(model_embeddings, safe_neg)  # [B, L, N]
        negative_logits = mask_negative_logits(
            negative_logits, negatives, self.negative_labels_ignore_index
        )
        positive_logits = self.logits_callback(model_embeddings, positive_labels)  # [B, L, P]

        # per-positive softmax over [positive, negatives]
        neg_lse = jax.nn.logsumexp(negative_logits, axis=-1, keepdims=True)  # [B, L, 1]
        denom = jnp.logaddexp(positive_logits, neg_lse)  # [B, L, P]
        nll = denom - positive_logits
        weights = self._label_weights(positive_labels, nll.dtype)
        mask = target_padding_mask.astype(nll.dtype) * weights
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def _label_weights(self, labels, dtype):
        return jnp.ones_like(labels, dtype=dtype)


class CESampledWeighted(CESampled):
    """CESampled with per-item weights applied to the positive terms."""

    def __init__(self, weight, negative_labels_ignore_index: int = -100) -> None:
        super().__init__(negative_labels_ignore_index)
        self.weight = jnp.asarray(weight)

    def _label_weights(self, labels, dtype):
        return self.weight[jnp.clip(labels, 0, self.weight.shape[0] - 1)].astype(dtype)
