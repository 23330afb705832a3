"""Cross-entropy losses: full-catalog and negative-sampled variants.

Capability parity with replay/nn/loss/ce.py:10-340 (CE, CEWeighted, CESampled,
CESampledWeighted).
"""

from __future__ import annotations

import math
from typing import Mapping, Optional, Tuple

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


# The routes of the full-softmax head. PLAIN writes the ``[B, L, I]`` logits
# (``get_logits`` + :func:`_softmax_nll`); FUSED keeps them in VMEM
# (``ops.fused_ce.fused_lse``: two Pallas kernels, two sweeps over the catalog: the
# forward's also gives ``softmax · W`` for ``dh``, the backward's gives ``dW``);
# SHARDED is FUSED under the trainer's mesh, each device's rows (and catalog shard)
# inside one ``shard_map`` (``parallel.sharded_ce.sharded_fused_lse``).
PLAIN, FUSED, SHARDED = "plain", "fused", "fused_sharded"

# The widest embedding at which :class:`CE` takes the fused route. Both routes cost
# ~ positions x items, so the width decides: the plain route's four passes over the
# float32 logits do not grow with it, the fused route's four products do (a
# contraction of up to 128 is one pass of the MXU, up to 256 two, 300 three).
# TPU v5e, 25,600 positions x 27,278 items, each head alone (value and both gradients,
# the label's logit and the mean included; 512 rows a program), ms a call, both rows
# from one run (PERF.md, Findings):
#
#     width    64      128     192     256     300
#     plain    15.61   15.75   15.86   16.00   16.22
#     fused     5.41    5.47    9.90    9.92   14.20
#
# The fused head is under the plain one at every width read, 300 included; the limit
# stays at 256, the widest at which a whole training step has been measured to gain,
# until a step at 300 is.
FUSED_MAX_WIDTH = 256


def _backend() -> str:
    return jax.default_backend()


def dtypes_sanctioned(hidden_dtype, table_dtype) -> bool:
    """Matching dtypes, or the flax compute-dtype split where one side is float32
    (the master table, or f32 hidden states) and the other a narrower float: the
    kernels accumulate in float32, exactly what ``get_logits``'s einsum promotion
    does (``Trainer(precision="bf16")``, docs/performance.md "The precision
    ladder"). An integer / quantized table or two different narrow floats are not."""
    h_dt, t_dt = jnp.dtype(hidden_dtype), jnp.dtype(table_dtype)
    floats = jnp.issubdtype(h_dt, jnp.floating) and jnp.issubdtype(t_dt, jnp.floating)
    return h_dt == t_dt or (floats and jnp.dtype(jnp.float32) in (h_dt, t_dt))


def full_softmax_route(
    *,
    backend: str,
    tying_head: bool,
    positives: int,
    rows: int,
    width: int,
    hidden_dtype=None,
    table_dtype=None,
    mesh_shape: Optional[Mapping[str, int]] = None,
    row_axes: Tuple[str, ...] = (),
    vocab_axis: Optional[str] = None,
) -> str:
    """The route :class:`CE` takes, from what it can observe when the step is traced.

    FUSED (SHARDED under a mesh of several devices) only when ALL hold: the backend
    is ``"tpu"`` (elsewhere the Pallas interpreter would stand in for the kernels);
    the model has a bias-free tying head over ``get_item_weights`` (``tying_head``:
    the trainer bound the table), so ``hidden . table^T`` IS ``get_logits``; one
    positive label; dtypes the kernels accumulate faithfully
    (:func:`dtypes_sanctioned`); the width is at most :data:`FUSED_MAX_WIDTH`; and,
    under a mesh, the mesh has the axes the trainer's rule table names and the
    flattened ``rows`` divide over the row axes (a Pallas call needs its
    ``shard_map``). Anything else: PLAIN.
    """
    if backend != "tpu" or not tying_head or positives != 1:
        return PLAIN
    if width > FUSED_MAX_WIDTH or not dtypes_sanctioned(hidden_dtype, table_dtype):
        return PLAIN
    mesh_shape = dict(mesh_shape or {})
    if math.prod(mesh_shape.values()) <= 1:
        return FUSED
    if vocab_axis not in mesh_shape or any(axis not in mesh_shape for axis in row_axes):
        return PLAIN
    if rows % math.prod(mesh_shape[axis] for axis in row_axes):
        return PLAIN
    return SHARDED


class CE(LossBase):
    """Full-softmax cross-entropy over the whole item catalog; the head CHOOSES its route.

    :func:`full_softmax_route` decides at trace time, from the backend, the model's
    head, the shapes and dtypes and the trainer's mesh (no option sets it):

    - PLAIN: ``get_logits`` writes the ``[B, L, I]`` logits; for the way back the head
      saves them and one log-sum-exp a row (:func:`_softmax_nll`), not the second
      tensor of the logits' shape that ``log_softmax``'s own rule keeps. Four
      HBM-bound passes over the logits a step (2.8 GB of float32 at 25,600 positions
      by 27,278 items), whatever the width.
    - FUSED / SHARDED: the log-sum-exp comes from ``ops.fused_ce.fused_lse`` (under a
      mesh inside ``parallel.sharded_ce.sharded_fused_lse``) and the label's logit
      from one gathered row: the logits never reach HBM. Taken on a TPU for a model
      with a bias-free tying head (``logits_via_item_weights``; the trainer then binds
      ``item_embeddings_callback``, the mesh and its axes) up to
      :data:`FUSED_MAX_WIDTH`, where the kernels' float32 products cost less than
      the plain route's passes.

    :attr:`route` is the route of the last trace; :class:`CEFused` and
    :class:`CEFusedTP` are this head with the route forced.
    """

    route = PLAIN

    def __init__(self) -> None:
        super().__init__()
        # bound by the trainer for a model that declares the tying head
        self.item_embeddings_callback = None
        self.mesh = None
        self.axis_name = "model"
        self.data_axis = "data"  # one mesh axis, a tuple of them, or None (rows replicated)
        # the kernels' parameters: CEFused's arguments; None = from the shapes
        self.tile: Optional[int] = None
        self.item_tile: Optional[int] = None
        self.interpret: Optional[bool] = None

    @property
    def avoid_full_logits(self) -> bool:
        """Whether the traced step never held ``[B, L, I]`` logits: health's
        logits statistics then stream over catalog chunks (``obs.health``)."""
        return self.route != PLAIN

    def _row_axes(self) -> Tuple[str, ...]:
        if self.data_axis is None:
            return ()
        return self.data_axis if isinstance(self.data_axis, tuple) else (self.data_axis,)

    def _choose_route(self, hidden: jnp.ndarray, positives: int) -> str:
        tying_head = self.item_embeddings_callback is not None
        # the table's shape and dtype without an op in the traced step
        table = jax.eval_shape(self.item_embeddings_callback) if tying_head else None
        return full_softmax_route(
            backend=_backend(),
            tying_head=tying_head,
            positives=positives,
            rows=math.prod(hidden.shape[:-1]),
            width=hidden.shape[-1],
            hidden_dtype=hidden.dtype,
            table_dtype=table.dtype if tying_head else None,
            mesh_shape=None if self.mesh is None else self.mesh.shape,
            row_axes=self._row_axes(),
            vocab_axis=self.axis_name,
        )

    def position_nll(self, model_embeddings, positive_labels):
        """``(labels [B, L], nll [B, L])``: the label's negative log-likelihood at
        every position, by the route :func:`full_softmax_route` chooses (sets
        :attr:`route`); no mask, no mean."""
        if positive_labels.shape[-1] != 1:
            msg = "Multi-positive labels are not supported by the CE loss"
            raise NotImplementedError(msg)
        self.route = self._choose_route(model_embeddings, positive_labels.shape[-1])
        if self.route == PLAIN:
            logits = self.logits_callback(model_embeddings)  # [B, L, I]
            labels = jnp.clip(positive_labels[..., 0], 0, logits.shape[-1] - 1)
            return labels, _softmax_nll(logits, labels)
        return self._fused_nll(model_embeddings, positive_labels)

    def __call__(
        self,
        model_embeddings,
        feature_tensors,
        positive_labels,
        negative_labels,
        padding_mask,
        target_padding_mask,
    ) -> jnp.ndarray:
        labels, nll = self.position_nll(model_embeddings, positive_labels)
        weights = self._label_weights(labels, nll.dtype)
        mask = target_padding_mask[..., 0].astype(nll.dtype) * weights
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def _label_weights(self, labels, dtype):
        return jnp.ones_like(labels, dtype=dtype)

    # -- the fused route ----------------------------------------------------- #
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
        """Reject dtype mismatches the kernel would silently paper over
        (:func:`dtypes_sanctioned`): a bug at the call site, named here instead
        of surfacing as a wrong-loss training run."""
        if not dtypes_sanctioned(hidden.dtype, table.dtype):
            msg = (
                f"{type(self).__name__}: hidden states are {jnp.dtype(hidden.dtype)} "
                f"but the item table is {jnp.dtype(table.dtype)}. Only matching "
                "dtypes, or the sanctioned "
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
        """``[N]`` catalog logsumexp by :attr:`route`."""
        from replay_tpu.ops.fused_ce import fused_lse, row_tile

        tile = row_tile(hidden2d.shape[0], self.tile)
        if self.route != SHARDED:
            return fused_lse(hidden2d, table, tile, self.item_tile, self._resolve_interpret())
        from replay_tpu.parallel.sharded_ce import sharded_fused_lse

        if self.mesh is None:
            msg = (
                f"{type(self).__name__} needs the device mesh to shard the rows "
                "and the catalog over: "
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
            tile=tile,
            item_tile=self.item_tile,
            interpret=self._resolve_interpret(),
        )

    def _fused_nll(self, model_embeddings, positive_labels):
        """``(labels, nll)`` with the log-sum-exp from the kernels and the label's
        logit from its one table row."""
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
        return labels, lse - label_logit


class CEFused(CE):
    """:class:`CE` with the FUSED route forced (the Pallas log-sum-exp head, one device).

    Bitwise-equivalent math to the plain route up to f32-vs-bf16 softmax precision
    (the fused path accumulates in f32 inside VMEM), but the ``[B, L, I]``
    logits tensor never reaches HBM — the dominant train-step traffic at
    full-catalog scales. Forcing is for a catalog whose logits do not fit, or a
    width past :data:`FUSED_MAX_WIDTH` where memory matters more than time;
    otherwise :class:`CE` takes this route where it is the faster one. Compiled on
    the ``"tpu"`` backend; on ``"cpu"`` the Pallas interpreter stands in (logged
    once at WARNING); any other backend raises unless ``interpret=`` is given
    (``ops.flash_attention.pallas_interpret``).

    Contract: the loss reconstructs logits as ``hidden · get_item_weights()ᵀ``,
    so it matches the plain route only for models whose ``get_logits`` is a
    BIAS-FREE tying head over that same table (SasRec/TiSasRec/Bert4Rec). Such
    models declare ``logits_via_item_weights = True``; the trainer refuses to
    bind CEFused to a model without that declaration (a model adding an item
    bias or scale would otherwise silently train with a different loss).
    """

    needs_item_embeddings = True
    requires_tying_head = True
    route = FUSED

    def __init__(
        self, tile: int = 256, item_tile: Optional[int] = None, interpret: bool = None
    ) -> None:
        super().__init__()
        self.tile = tile
        self.item_tile = item_tile
        self.interpret = interpret

    def _choose_route(self, hidden: jnp.ndarray, positives: int) -> str:
        return type(self).route  # forced: CEFusedTP's too


class CEFusedTP(CEFused):
    """:class:`CE` with the SHARDED route forced: the fused head under a mesh, the
    item table sharded over the mesh's TP axis.

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
    route = SHARDED

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


def exit_distribution(gate_logits: jnp.ndarray) -> jnp.ndarray:
    """``p`` [T, ...] from the exit gates' logits [T, ...]: the chance that the
    loop stops after step t, ``lambda_t prod_{j<t} (1 - lambda_j)`` with
    ``lambda = sigmoid(logits)``, and all that is left at the last step
    (``prod_{j<T} (1 - lambda_j)``; the last gate is never read). Sums to 1."""
    stops = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    steps, survived, out = gate_logits.shape[0], jnp.ones_like(stops[0]), []
    for t in range(steps - 1):
        out.append(stops[t] * survived)
        survived = survived * (1.0 - stops[t])
    return jnp.stack(out + [survived])


class ExitWeightedCE(CE):
    """Full-softmax CE at EVERY exit of a looped model, weighted by the exit
    gate's distribution, minus ``entropy_weight`` times that distribution's
    entropy (the entropy-regularised objective of "Scaling Latent Reasoning via
    Looped Language Models", 2025; the model: ``HybridRec(loop_steps=T)``,
    T > 1). Per valid target, in float32:

        loss = mean [ sum_t p(t) * CE(h_t . table^T, y)  -  beta * H(p) ]

    ``p`` from :func:`exit_distribution`. The trainer binds :attr:`exits` (the
    model's sown ``hidden`` [T, B, L, d] and ``gate_logits`` [T, B, L]); each
    exit's head takes :class:`CE`'s route (:meth:`CE.position_nll`, under the
    scope ``exit_head``), so a narrow model gets the fused head at every exit.
    The hidden states handed as ``model_embeddings`` (the last step's) are not
    read. ``step_counters`` (the trainer folds them into the step's
    ``counters``): ``exit_mass`` [T], the mean of ``p(t)`` over the valid
    targets, and ``exit_loss`` [T], each exit's mean CE there.
    """

    sows_counters = True

    def __init__(self, entropy_weight: float = 0.1) -> None:
        super().__init__()
        self.entropy_weight = entropy_weight
        self.exits = None  # bound by the trainer for a model that sows them
        self.step_counters = {}

    def __call__(
        self,
        model_embeddings,
        feature_tensors,
        positive_labels,
        negative_labels,
        padding_mask,
        target_padding_mask,
    ) -> jnp.ndarray:
        if self.exits is None:
            msg = (
                "ExitWeightedCE needs the exits of a looped model: train "
                "HybridRec(loop_steps > 1) through replay_tpu.nn.Trainer, which "
                "binds loss.exits"
            )
            raise AttributeError(msg)
        hidden = self.exits["hidden"]
        losses = []
        for t in range(hidden.shape[0]):
            with jax.named_scope("exit_head"):
                labels, nll = self.position_nll(hidden[t], positive_labels)
            losses.append(nll.astype(jnp.float32))
        nll = jnp.stack(losses)  # [T, B, L]
        mask = target_padding_mask[..., 0].astype(jnp.float32)
        mask = mask * self._label_weights(labels, jnp.float32)
        count = jnp.maximum(jnp.sum(mask), 1.0)
        p = exit_distribution(self.exits["gate_logits"])
        entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, jnp.finfo(jnp.float32).tiny)), axis=0)
        per_target = jnp.sum(p * nll, axis=0) - self.entropy_weight * entropy
        self.step_counters = {
            "exit_mass": jnp.sum(p * mask, axis=(1, 2)) / count,
            "exit_loss": jnp.sum(nll * mask, axis=(1, 2)) / count,
        }
        return jnp.sum(per_target * mask) / count


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
