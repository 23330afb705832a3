"""Composable batch transforms over dict batches.

Capability parity with replay/nn/transform/*.py (~830 LoC): NextToken, negative
sampling (uniform + multi-class), TokenMask, SequenceRoll, Trim/AdaptiveTrim,
EqualityMask, Copy, Rename, Select, Unsqueeze, Group, composed per split.

JAX design: every transform is a pure callable ``batch, rng -> batch`` (no module
state); randomness comes from an explicit PRNG key threaded by :class:`Compose`.
The pipeline runs on the feeder thread beside a chip that is busy with the step,
so the contract is (docs/performance.md "Closing the dispatch gap"):

* **numpy in, numpy out.** A deterministic transform works in the array
  namespace of the leaf it is given (:func:`_namespace`): a numpy leaf stays
  numpy and no device is touched; a ``jax.Array`` or a tracer gets ``jax.numpy``.
  A bare ``jnp`` call on a host leaf is an eager device program whose result
  ``Trainer._stack_chunk`` has to read back, behind the running scan.
* **A random draw is ONE compiled program.** A stochastic transform keeps its
  draw and everything that depends on it in one ``jax.jit`` kernel built once
  per instance, and :class:`Compose` compiles the run of transforms from the
  first stochastic one on (its own key splits included) as one program: one
  dispatch a batch instead of one per ``jnp`` call.
* **No host read of a device value.** The caller's key lives on the chip, queued
  behind the scan: reading it (``np.asarray(key)``, ``jax.random.key_data``,
  ``device_get``) to "draw the mask in numpy" stalls the feeder a whole chunk a
  batch. The bits are made and used on the device; what does not depend on them
  never leaves the host.

All ops are static-shape except ``AdaptiveTrimTransform``, which is host-side only
(data-dependent length) and documented as such.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from replay_tpu.obs.trace import stage

DEFAULT_MASK_POSTFIX = "_mask"
Batch = Dict[str, jnp.ndarray]


def _namespace(*leaves):
    """``numpy`` while every leaf is a host array, ``jax.numpy`` once one is a
    ``jax.Array`` (a tracer is one): the result lives where its input lives."""
    return jnp if any(isinstance(leaf, jax.Array) for leaf in leaves) else np


class Transform:
    """Base: a pure batch→batch function; ``needs_rng`` marks stochastic transforms."""

    needs_rng = False

    def __call__(self, batch: Batch, rng: Optional[jax.Array] = None) -> Batch:
        raise NotImplementedError


class Compose(Transform):
    """Apply transforms in order, splitting the rng across the stochastic ones.

    The deterministic transforms ahead of the first stochastic one run one by
    one where their leaves live (host leaves: no device program). From the first
    stochastic transform on, the rest of the pipeline is ONE compiled program
    ``(rng, leaves) -> new leaves``, its ``jax.random.split`` per stochastic
    transform inside it, cached by the batch's structure and shapes. A leaf the
    run hands through untouched comes back as the object that went in (a numpy
    array stays one), so put what needs no random bits ahead of the first draw.

    Each step runs as a ``transform`` stage (``obs.trace.stage``) that carries
    the class name (the compiled run: its classes joined by ``+``) and
    ``device_programs``, the compiled programs ``Compose`` dispatched in it (0
    on the host, 1 for the run): in a profiler capture and in the chunk stage
    log's ``transform_by_name`` / ``transform_device_programs`` the cost of the
    input pipeline reads per transform. A deterministic transform that its
    caller hands ``jax.Array`` leaves works on them in eager ``jax.numpy``;
    those programs are not counted here (``device_leaves`` of ``stack`` sees
    their results)."""

    def __init__(self, transforms: Sequence[Transform]) -> None:
        self.transforms = list(transforms)
        # how many run ahead of the compiled run: up to the first stochastic one
        self._host = next(
            (i for i, t in enumerate(self.transforms) if t.needs_rng), len(self.transforms)
        )
        self._run_name = "+".join(type(t).__name__ for t in self.transforms[self._host :])
        self._programs: Dict[tuple, tuple] = {}

    @property
    def needs_rng(self) -> bool:  # type: ignore[override]
        return self._host < len(self.transforms)

    def __call__(self, batch: Batch, rng: Optional[jax.Array] = None) -> Batch:
        for transform in self.transforms[: self._host]:
            with stage("transform", transform=type(transform).__name__, device_programs=0):
                batch = transform(batch)
        if not self.needs_rng:
            return batch
        if rng is None:
            msg = f"{type(self.transforms[self._host]).__name__} needs an rng key"
            raise ValueError(msg)
        dispatched = int(not isinstance(rng, jax.core.Tracer))  # inlined under a caller's jit
        with stage("transform", transform=self._run_name, device_programs=dispatched):
            return self._compiled_run(batch, rng)

    def _compiled_run(self, batch: Batch, rng: jax.Array) -> Batch:
        leaves, treedef = jax.tree.flatten(batch)
        # read from attributes: np.result_type of a jax.Array would read it back
        key = (
            treedef,
            tuple((np.shape(leaf), getattr(leaf, "dtype", type(leaf))) for leaf in leaves),
        )
        if key not in self._programs:
            self._programs[key] = self._compile_run(treedef)
        program, plan = self._programs[key]
        made = iter(program(rng, leaves))
        return jax.tree.unflatten(
            plan["treedef"],
            [next(made) if source is None else leaves[source] for source in plan["sources"]],
        )

    def _compile_run(self, treedef):
        """The jitted run for one batch structure, and its plan: the output's
        treedef and, per output leaf, the index of the caller's own leaf that is
        handed through, or ``None`` for the program's next result (the tracing
        fills it in: a leaf the transforms did not touch IS the tracer that came
        in). jit leaves an argument nothing reads on the host."""
        plan: Dict[str, object] = {}

        def program(rng, leaves):
            batch = jax.tree.unflatten(treedef, leaves)
            for transform in self.transforms[self._host :]:
                if transform.needs_rng:
                    rng, sub = jax.random.split(rng)
                    batch = transform(batch, sub)
                else:
                    batch = transform(batch)
            out_leaves, plan["treedef"] = jax.tree.flatten(batch)
            sources = plan["sources"] = [
                next((i for i, leaf in enumerate(leaves) if leaf is out), None)
                for out in out_leaves
            ]
            return [out for out, source in zip(out_leaves, sources) if source is None]

        return jax.jit(program), plan


class NextTokenTransform(Transform):
    """Shift ``label_name`` by ``shift`` to build ``positive_labels`` (+ its mask);
    trim the last ``shift`` steps off the declared sequential features.

    ``apply_to`` names the sequential features (and their masks) to trim — only
    those are touched, so non-sequence [B, N] tensors such as sampled
    ``negative_labels`` pass through untouched (the reference trims only schema
    sequential features). When ``apply_to`` is None every ndim>=2 tensor not in
    ``ignore`` is trimmed, which is only safe if the batch holds nothing but
    sequences.
    """

    def __init__(
        self,
        label_name: str,
        shift: int = 1,
        ignore: Union[List[str], str, None] = None,
        apply_to: Union[List[str], str, None] = None,
        out_feature_name: str = "positive_labels",
        mask_postfix: str = DEFAULT_MASK_POSTFIX,
    ) -> None:
        self.label_name = label_name
        self.shift = shift
        self.ignore = [ignore] if isinstance(ignore, str) else list(ignore or [])
        if apply_to is not None:
            apply_to = [apply_to] if isinstance(apply_to, str) else list(apply_to)
            # trim a feature's mask together with the feature
            apply_to = list(
                dict.fromkeys(apply_to + [f"{name}{mask_postfix}" for name in apply_to])
            )
        self.apply_to = apply_to
        self.out_feature_name = out_feature_name
        self.mask_postfix = mask_postfix

    def _should_trim(self, name: str, value) -> bool:
        if name in self.ignore or value.ndim < 2:
            return False
        if self.apply_to is not None:
            return name in self.apply_to
        return True

    def __call__(self, batch: Batch, rng=None) -> Batch:
        shift = self.shift
        labels = batch[self.label_name][:, shift:]
        label_mask_name = f"{self.label_name}{self.mask_postfix}"
        out = {}
        for name, value in batch.items():
            out[name] = value[:, :-shift] if self._should_trim(name, value) else value
        out[self.out_feature_name] = labels
        if label_mask_name in batch:
            out[f"{self.out_feature_name}{self.mask_postfix}"] = batch[label_mask_name][:, shift:]
        else:
            out[f"{self.out_feature_name}{self.mask_postfix}"] = _namespace(labels).ones_like(
                labels, dtype=bool
            )
        return out


class UniformNegativeSamplingTransform(Transform):
    """Sample ``num_negative_samples`` global negatives per batch (without replacement)."""

    needs_rng = True

    def __init__(
        self,
        cardinality: int,
        num_negative_samples: int,
        *,
        out_feature_name: str = "negative_labels",
        sample_distribution: Optional[jnp.ndarray] = None,
    ) -> None:
        if num_negative_samples >= cardinality:
            msg = (
                f"num_negative_samples ({num_negative_samples}) must be < cardinality "
                f"({cardinality})"
            )
            raise ValueError(msg)
        if sample_distribution is not None and sample_distribution.shape[-1] != cardinality:
            msg = "sample_distribution size must match cardinality"
            raise ValueError(msg)
        self.cardinality = cardinality
        self.num_negative_samples = num_negative_samples
        self.out_feature_name = out_feature_name
        if sample_distribution is not None:
            sample_distribution = jnp.asarray(sample_distribution)  # on the device once
        self.sample_distribution = sample_distribution
        self._kernel = jax.jit(self._draw)

    def _draw(self, rng, distribution):
        probs = None if distribution is None else distribution / jnp.sum(distribution)
        return jax.random.choice(
            rng, self.cardinality, shape=(self.num_negative_samples,), replace=False, p=probs
        )

    def __call__(self, batch: Batch, rng=None) -> Batch:
        return {**batch, self.out_feature_name: self._kernel(rng, self.sample_distribution)}


class MultiClassNegativeSamplingTransform(Transform):
    """Per-row negatives sampled from class-conditional distributions.

    ``class_assignment`` maps each item to a class; for each batch row the sampler
    draws negatives from the items of the same class as the row's reference item
    (reference: replay/nn/transform/negative_sampling.py:82).
    """

    needs_rng = True

    def __init__(
        self,
        class_assignment: jnp.ndarray,  # [num_items] int class per item
        num_negative_samples: int,
        reference_name: str = "item_id",
        out_feature_name: str = "negative_labels",
    ) -> None:
        class_assignment = np.asarray(class_assignment)
        self.class_assignment = jnp.asarray(class_assignment)
        self.num_negative_samples = num_negative_samples
        self.reference_name = reference_name
        self.out_feature_name = out_feature_name
        # per-class item-id index lists padded to the largest class: sampling draws a
        # random index into the class's list instead of materializing a [B, num_items]
        # probability matrix (which would blow up memory on large catalogs)
        num_classes = int(class_assignment.max()) + 1
        members = [np.flatnonzero(class_assignment == c) for c in range(num_classes)]
        empty = [c for c, m in enumerate(members) if len(m) == 0]
        if empty:
            msg = (
                f"class_assignment has empty classes {empty}: every draw for such a "
                "class would silently return item 0. Use contiguous class ids."
            )
            raise ValueError(msg)
        sizes = np.array([len(m) for m in members], dtype=np.int32)
        table = np.zeros((num_classes, int(sizes.max())), dtype=np.int32)
        for c, m in enumerate(members):
            if len(m):
                table[c, : len(m)] = m
        self._class_items = jnp.asarray(table)  # [num_classes, max_class_size]
        self._class_sizes = jnp.asarray(sizes)  # [num_classes]
        self._kernel = jax.jit(self._draw)

    def _draw(self, rng, last_items, class_assignment, class_items, class_sizes):
        # the tables are arguments, not constants of the program: they stay on
        # the device and a catalog-sized one is not baked into the executable
        classes = class_assignment[jnp.clip(last_items, 0, class_assignment.shape[0] - 1)]
        draws = jax.random.randint(
            rng, (classes.shape[0], self.num_negative_samples), 0, jnp.iinfo(jnp.int32).max
        )
        indices = draws % class_sizes[classes][:, None]
        return jnp.take_along_axis(class_items[classes], indices, axis=1)

    def __call__(self, batch: Batch, rng=None) -> Batch:
        reference = batch[self.reference_name]
        last_items = reference[:, -1] if reference.ndim > 1 else reference
        negatives = self._kernel(
            rng, last_items, self.class_assignment, self._class_items, self._class_sizes
        )
        return {**batch, self.out_feature_name: negatives}


class InBatchNegativeSamplingTransform(Transform):
    """Use the batch's own positives as the shared negative pool (two-tower
    retrieval training: every query scores against every other query's target).

    Emits ``out_feature_name`` of shape [B] — the `[N]` shared-pool form the
    sampled losses broadcast; own-positive collisions stay in the denominator,
    the standard in-batch-softmax formulation.
    """

    def __init__(
        self,
        label_name: str = "positive_labels",
        out_feature_name: str = "negative_labels",
    ) -> None:
        self.label_name = label_name
        self.out_feature_name = out_feature_name

    def __call__(self, batch: Batch, rng=None) -> Batch:
        labels = batch[self.label_name]
        while labels.ndim > 1:  # [B, L, P] -> last position's positive per row
            labels = labels[:, -1]
        return {**batch, self.out_feature_name: labels}


class SegmentBoundaryMaskTransform(Transform):
    """Packed-batch fixup after :class:`NextTokenTransform`: mask labels that
    cross a segment boundary, and trim ``segment_ids`` to the input length.

    A packed row concatenates several user sequences (segment ids 1..k, 0 on
    padding). The next-token shift assigns position ``t`` the label at
    original position ``t + shift`` — at the last positions of a segment that
    label belongs to the NEXT user's sequence. This transform ANDs the target
    mask with "label position is in the SAME (non-padding) segment as the
    input position", so the loss never trains across a packed boundary, then
    replaces the full-length ``segment_ids`` with its input-aligned
    ``[:, :-shift]`` view (what the model's attention mask consumes).
    Run it after the rename that produced ``mask_name`` and before the
    unsqueeze/group steps.
    """

    def __init__(
        self,
        segment_name: str = "segment_ids",
        mask_name: str = "target_padding_mask",
        shift: int = 1,
    ) -> None:
        if shift < 1:
            msg = "shift must be >= 1 (the NextTokenTransform shift)"
            raise ValueError(msg)
        self.segment_name = segment_name
        self.mask_name = mask_name
        self.shift = shift

    def __call__(self, batch: Batch, rng=None) -> Batch:
        segments = batch[self.segment_name]
        shift = self.shift
        if segments.shape[-1] == batch[self.mask_name].shape[1]:
            msg = (
                f"'{self.segment_name}' is already trimmed to the label "
                f"length; run {type(self).__name__} on the FULL-length "
                "segment ids (before any trim), after NextTokenTransform "
                f"excluded '{self.segment_name}' from apply_to."
            )
            raise ValueError(msg)
        inputs = segments[:, :-shift]
        labels_seg = segments[:, shift:]
        same_segment = (inputs == labels_seg) & (labels_seg != 0) & (inputs != 0)
        return {
            **batch,
            self.mask_name: batch[self.mask_name] & same_segment,
            self.segment_name: inputs,
        }


class TokenMaskTransform(Transform):
    """BERT-style keep-mask: True = visible token, False = masked-out token.

    Corner-case handling mirrors the reference (replay/nn/transform/token_mask.py:44):
    a row with nothing masked gets its LAST valid token masked; a row with everything
    masked gets its second-to-last position kept.
    """

    needs_rng = True

    def __init__(
        self,
        token_name: str,
        out_feature_name: str = "token_mask",
        mask_prob: float = 0.15,
        mask_postfix: str = DEFAULT_MASK_POSTFIX,
    ) -> None:
        self.token_name = token_name
        self.out_feature_name = out_feature_name
        self.mask_prob = mask_prob
        self.mask_postfix = mask_postfix
        self._kernel = jax.jit(self._keep)

    def __call__(self, batch: Batch, rng=None) -> Batch:
        padding = batch[self.token_name]
        if padding.dtype != jnp.bool_:
            msg = "Source tensor for token mask must be boolean (a padding mask)."
            raise ValueError(msg)
        return {**batch, self.out_feature_name: self._kernel(rng, padding)}

    def _keep(self, rng, padding):
        """``(rng, padding) -> keep``: the draw, the comparison and both repairs."""
        uniform = jax.random.uniform(rng, padding.shape)
        keep = (uniform * padding) >= self.mask_prob  # padded positions always False

        valid_count = padding.sum(axis=1)
        kept_count = (keep & padding).sum(axis=1)
        # nothing masked -> mask the last valid position
        all_kept = kept_count == valid_count
        last_valid = padding.shape[1] - 1 - jnp.argmax(padding[:, ::-1], axis=1)
        rows = jnp.arange(padding.shape[0])
        keep = keep.at[rows, last_valid].set(
            jnp.where(all_kept, False, keep[rows, last_valid])
        )
        # everything masked -> keep the position before the last valid one
        none_kept = (kept_count == 0) & (valid_count > 1)
        before_last = jnp.maximum(last_valid - 1, 0)
        keep = keep.at[rows, before_last].set(
            jnp.where(none_kept, True, keep[rows, before_last])
        )
        return keep


class SequenceRollTransform(Transform):
    """Roll a sequence along the time axis, refilling the vacated slots with padding."""

    def __init__(self, feature_name: str, roll: int = 1, padding_value: int = 0) -> None:
        if roll == 0:
            msg = "roll must be non-zero"
            raise ValueError(msg)
        self.feature_name = feature_name
        self.roll = roll
        self.padding_value = padding_value

    def __call__(self, batch: Batch, rng=None) -> Batch:
        value = batch[self.feature_name]
        xp = _namespace(value)
        # at most the whole axis: past its length nothing of the sequence is left
        roll = max(min(self.roll, value.shape[1]), -value.shape[1])
        vacated = xp.full(
            (value.shape[0], abs(roll)) + value.shape[2:], self.padding_value, dtype=value.dtype
        )
        if roll > 0:
            rolled = xp.concatenate([vacated, value[:, : value.shape[1] - roll]], axis=1)
        else:
            rolled = xp.concatenate([value[:, -roll:], vacated], axis=1)
        return {**batch, self.feature_name: rolled}


class TrimTransform(Transform):
    """Keep the LAST ``seq_len`` positions of the named (left-padded) sequences."""

    def __init__(self, seq_len: int, feature_names: Union[List[str], str]) -> None:
        self.seq_len = seq_len
        self.feature_names = [feature_names] if isinstance(feature_names, str) else list(feature_names)

    def __call__(self, batch: Batch, rng=None) -> Batch:
        out = dict(batch)
        for name in self.feature_names:
            if batch[name].shape[1] < self.seq_len:
                msg = f"Cannot trim '{name}' of length {batch[name].shape[1]} to {self.seq_len}"
                raise ValueError(msg)
            out[name] = batch[name][:, -self.seq_len :]
        return out


class AdaptiveTrimTransform(Transform):
    """Trim to the batch's longest real sequence. HOST-ONLY: data-dependent shape,
    do not use inside jit (reference: replay/nn/transform/trim.py:50)."""

    def __init__(self, feature_names: Union[List[str], str], padding_mask_name: str = "padding_mask") -> None:
        self.feature_names = [feature_names] if isinstance(feature_names, str) else list(feature_names)
        self.padding_mask_name = padding_mask_name

    def __call__(self, batch: Batch, rng=None) -> Batch:
        if self.padding_mask_name not in batch:
            msg = f"Padding mask '{self.padding_mask_name}' not found in batch."
            raise KeyError(msg)
        mask = batch[self.padding_mask_name]
        max_len = int(mask.sum(axis=1).max())
        if max_len == mask.shape[1]:
            return batch
        out = dict(batch)
        for name in self.feature_names:
            out[name] = batch[name][:, -max_len:]
        return out


class EqualityMaskTransform(Transform):
    """Combine ``mask_name`` with (feature == value) under AND/OR/XOR."""

    _OPS = {"and": "logical_and", "or": "logical_or", "xor": "logical_xor"}

    def __init__(self, feature_name: str, mask_name: str, equality_value, op: str = "and") -> None:
        if op not in self._OPS:
            msg = f"op must be one of {sorted(self._OPS)}"
            raise ValueError(msg)
        self.feature_name = feature_name
        self.mask_name = mask_name
        self.equality_value = equality_value
        self.op = op

    def __call__(self, batch: Batch, rng=None) -> Batch:
        mask = batch[self.mask_name]
        modification = batch[self.feature_name] == self.equality_value
        combine = getattr(_namespace(mask, modification), self._OPS[self.op])
        return {**batch, self.mask_name: combine(mask, modification)}


class CopyTransform(Transform):
    def __init__(self, mapping: Dict[str, str]) -> None:
        self.mapping = mapping

    def __call__(self, batch: Batch, rng=None) -> Batch:
        out = dict(batch)
        for src, dst in self.mapping.items():
            out[dst] = batch[src]
        return out


class RenameTransform(Transform):
    def __init__(self, mapping: Dict[str, str]) -> None:
        self.mapping = mapping

    def __call__(self, batch: Batch, rng=None) -> Batch:
        out = {}
        for name, value in batch.items():
            out[self.mapping.get(name, name)] = value
        return out


class SelectTransform(Transform):
    def __init__(self, feature_names: List[str]) -> None:
        self.feature_names = list(feature_names)

    def __call__(self, batch: Batch, rng=None) -> Batch:
        return {name: batch[name] for name in self.feature_names}


class UnsqueezeTransform(Transform):
    def __init__(self, feature_name: str, axis: int = -1) -> None:
        self.feature_name = feature_name
        self.axis = axis

    def __call__(self, batch: Batch, rng=None) -> Batch:
        value = batch[self.feature_name]
        return {**batch, self.feature_name: _namespace(value).expand_dims(value, self.axis)}


class GroupTransform(Transform):
    """Nest the named features under a sub-dict key (e.g. ``feature_tensors``)."""

    def __init__(self, mapping: Dict[str, List[str]]) -> None:
        self.mapping = mapping

    def __call__(self, batch: Batch, rng=None) -> Batch:
        grouped_names = {name for names in self.mapping.values() for name in names}
        out = {name: value for name, value in batch.items() if name not in grouped_names}
        for group, names in self.mapping.items():
            out[group] = {name: batch[name] for name in names if name in batch}
        return out
