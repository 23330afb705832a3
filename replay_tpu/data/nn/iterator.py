"""Fixed-shape batches from per-query sequences.

Capability parity with replay/data/nn/torch_sequential_dataset.py:29-302 (left-pad
to ``max_sequence_length``, sliding-window expansion of long histories, validation
variant carrying padded ground-truth/train id sets) and the exact-batch semantics
of the parquet pipeline (fixed_batch_dataset.py:68, compute_length.py:62).

TPU design: XLA wants ONE shape for the whole epoch, so every batch is exactly
``[batch_size, max_sequence_length]`` — the final short batch is padded with
repeated rows and flagged via a ``valid`` row mask that zeroes their loss and
metric contributions. Sharding across hosts happens here through the
:class:`~replay_tpu.data.nn.partitioning.Partitioning` seam (every replica sees a
disjoint strided slice); sharding across a host's chips happens later via
NamedSharding in the trainer.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

if TYPE_CHECKING:
    from replay_tpu.obs.trace import Tracer

from replay_tpu.data.nn.partitioning import Partitioning
from replay_tpu.data.nn.sequential_dataset import SequentialDataset
from replay_tpu.obs.trace import stage

# id-set padding sentinels for validation batches (MetricsBuilder's contract).
# The reference needs distinct -1/-2 because its ground-truth and train id
# sets can ride one tensor (torch_sequential_dataset.py:179-180); here they
# are separate arrays, so both sentinels are any-negative — kept as named
# constants for reference-API familiarity.
DEFAULT_GROUND_TRUTH_PADDING_VALUE = -1
DEFAULT_TRAIN_PADDING_VALUE = -1

Batch = Dict[str, np.ndarray]


def _span_index(lengths: np.ndarray, max_len: int, stride: Optional[int]) -> np.ndarray:
    """``[entries, 3]`` (row, start, stop) over rows of the given ``lengths``, rows
    in order. With a ``stride``: the windows of ``max_len`` covering each row, the
    LAST one always ending at the sequence end (recency matters for next-item
    training). With none: one entry a row, its last ``max_len`` events."""
    if stride is None:
        row, stop = np.arange(len(lengths)), lengths
    else:
        counts = 1 + -(-np.maximum(lengths - max_len, 0) // stride)
        row = np.repeat(np.arange(len(lengths)), counts)
        ordinal = np.arange(len(row)) - np.repeat(np.cumsum(counts) - counts, counts)
        stop = np.minimum(max_len + ordinal * stride, lengths[row])
    start = np.maximum(stop - max_len, 0)
    return np.stack([row, start, stop], axis=1).astype(np.int64)


def _first_values(column: np.ndarray) -> np.ndarray:
    """The first value of every row of a scalar feature's column, as ONE array:
    typed where the values are numbers, boxed (``object``) otherwise, for
    :func:`_take`."""
    values = [np.asarray(value).reshape(-1)[0] for value in column]
    typed = np.asarray(values)
    return typed if typed.dtype.kind in "biuf" else np.asarray(values, dtype=object)


def _take(column: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """``column[rows]``; of a boxed column (string ids) the dtype ``np.asarray``
    infers from THOSE rows' values, which is what a list of them gave."""
    taken = column[rows]
    return np.asarray(taken.tolist()) if taken.dtype == object else taken


@dataclass
class SequenceBatcher:
    """Iterates fixed-shape raw batches ``{feature: [B, L], feature_mask: [B, L]}``.

    The output feeds the transform pipelines (replay_tpu.nn.transform.template)
    unchanged — masks are emitted per feature under ``<name>_mask``.

    Batch assembly touches no pandas object and runs no Python loop over rows:
    everything a batch needs of a row is an array made ONCE at construction —
    per sequence feature the flat values and row offsets
    (``SequentialDataset.get_all_sequences``: one ``to_numpy()`` a column), the
    ``(row, start, stop)`` index ``_entries`` of every window (from the item
    sequences' lengths), the query ids and each scalar feature's first values.
    A batch is index arithmetic on ``spans = _entries[chunk]`` and one native
    gather a sequence feature. What falls back: a sequence feature whose dtype
    the native gather does not take (neither integer nor floating) is
    assembled row by row through ``SequentialDataset.get_sequence``, and the
    ``batch_build`` stage reports it as ``python_rows`` (0 otherwise), summed a
    chunk as ``batch_build_python_rows`` in the chunk stage log.

    :param windows: expand sequences longer than ``max_sequence_length`` into
        several windows (training); when False only the LAST ``max_sequence_length``
        events are kept (inference — the reference predict path).
    :param partitioning: replica-sharding seam; defaults to the single-replica
        identity partitioning.
    :param bucket_boundaries: optional ascending lengths (e.g. ``(16, 50)``)
        enabling length-bucketed batching: each entry lands in the smallest
        bucket holding it, and every batch is padded only to ITS bucket's
        length (the SURVEY §7 padding-waste mitigation). XLA compiles one
        program per distinct shape — a handful of buckets, not per-batch
        dynamic shapes. ``max_sequence_length`` remains the top bucket.
        Incompatible with the scan-chunked fit (see :attr:`scan_compatible`).
    :param tracer: optional :class:`replay_tpu.obs.Tracer`. Every batch
        assembly is a ``batch_build`` stage (``obs.trace.stage``: in any
        profiler capture and in the chunk stage log without this argument);
        it is recorded by this tracer when given, else by the tracer of the
        traced ``fit`` that consumes the batches — inside its ``data_wait``
        phase it shows how much is THIS batcher (gather/pad) versus upstream
        iteration; on a prefetch or feeder thread the spans land on that
        thread's timeline in ``trace.json``.
    """

    dataset: SequentialDataset
    batch_size: int
    max_sequence_length: int
    windows: bool = False
    window_stride: Optional[int] = None
    shuffle: bool = False
    seed: int = 0
    partitioning: Optional[Partitioning] = None
    epoch: int = field(default=0)
    bucket_boundaries: Optional[Sequence[int]] = None
    tracer: Optional["Tracer"] = None

    def __post_init__(self) -> None:
        if (
            self.bucket_boundaries
            and self.partitioning is not None
            and self.partitioning.replicas.num_replicas > 1
        ):
            # bucketed widths/step counts differ per replica, breaking the
            # same-shape-per-step collective invariant (partitioning.py)
            msg = (
                "bucket_boundaries cannot be combined with multi-replica "
                "partitioning: hosts would emit differing batch shapes/counts. "
                "Use fixed-shape batches for multi-host training."
            )
            raise ValueError(msg)
        with stage("batcher_init", tracer=self.tracer):
            self._index_rows()

    def _index_rows(self) -> None:
        self._schema = self.dataset.schema
        self._seq_names = [f.name for f in self._schema.all_features if f.is_seq]
        self._scalar_names = [f.name for f in self._schema.all_features if not f.is_seq]
        # everything a batch needs of a row is an array made ONCE, here: batch
        # assembly touches no pandas object and runs no Python loop over rows.
        # The flat+offsets layout per sequence feature feeds the native gather.
        self._flat: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
        self._dtypes: Dict[str, type] = {}  # int32 or float32, by the FIRST row's dtype
        for name in self._seq_names:
            sequences = [
                np.asarray(sequence).reshape(-1)
                for sequence in self.dataset.get_all_sequences(name)
            ]
            first = sequences[0] if sequences else np.zeros(0)
            self._dtypes[name] = (
                np.int32 if np.issubdtype(first.dtype, np.integer) else np.float32
            )
            row_lengths = np.fromiter(map(len, sequences), np.int64, count=len(sequences))
            offsets = np.concatenate([[0], np.cumsum(row_lengths)])
            flat = (
                np.concatenate(sequences) if sequences else np.zeros(0, np.int64)
            )
            if np.issubdtype(flat.dtype, np.integer):
                flat = np.ascontiguousarray(flat, np.int64)  # kernel dtype, once
            elif np.issubdtype(flat.dtype, np.floating):
                flat = np.ascontiguousarray(flat, np.float64)
            else:
                continue  # exotic dtype: the per-row python path handles it
            self._flat[name] = (flat, offsets)
        # rows a batch assembles in the per-row python loop (the stage's counter)
        self._python_rows = self.batch_size if len(self._flat) < len(self._seq_names) else 0
        self._scalars = {
            name: _first_values(self.dataset.get_all_sequences(name))
            for name in self._scalar_names
        }
        self._query_ids = self.dataset.query_ids
        # the item sequences define the windows, in the schema or not
        item_sequences = self.dataset.get_all_sequences(self.dataset.item_id_column)
        lengths = np.fromiter(map(len, item_sequences), np.int64, count=len(item_sequences))
        stride = (self.window_stride or self.max_sequence_length) if self.windows else None
        self._entries = _span_index(lengths, self.max_sequence_length, stride)

    def _buckets(self) -> List[int]:
        # boundaries above max_sequence_length would out-grow positional tables
        boundaries = sorted(
            b for b in set(self.bucket_boundaries or ()) if b < self.max_sequence_length
        )
        boundaries.append(self.max_sequence_length)
        return boundaries

    def _bucket_ids(self, entries: np.ndarray, boundaries: List[int]) -> np.ndarray:
        """Vectorized: bucket index of every (row, start, stop) entry."""
        lengths = np.minimum(entries[:, 2] - entries[:, 1], self.max_sequence_length)
        return np.searchsorted(np.asarray(boundaries), lengths, side="left")

    def __len__(self) -> int:
        """Number of fixed-size batches for THIS replica (ceil semantics)."""
        from replay_tpu.data.batching import uniform_batch_count

        part = self.partitioning or Partitioning()
        order = part.generate(len(self._entries), self.epoch)
        if not self.bucket_boundaries:
            return uniform_batch_count(len(order), self.batch_size)
        bucket_ids = self._bucket_ids(self._entries[order], self._buckets())
        counts = np.bincount(bucket_ids)
        return int(sum(uniform_batch_count(int(n), self.batch_size) for n in counts if n))

    def set_epoch(self, epoch: int) -> None:
        """Advance the shuffle epoch (folds into the partitioning seed)."""
        self.epoch = epoch

    @property
    def scan_compatible(self) -> bool:
        """Whether every emitted batch shares ONE ``[B, L]`` shape — the
        precondition for the scan-chunked fit (``Trainer.fit(scan_chunk=...)``
        stacks K batches into one ``[K, B, L]`` program input). Length
        bucketing emits a SET of widths, so a bucketed batcher is not scan
        compatible; ``Trainer.fit`` rejects the combination at fit start."""
        return not self.bucket_boundaries

    def _entry_order(self) -> np.ndarray:
        part = self.partitioning or Partitioning(shuffle=self.shuffle, seed=self.seed)
        if self.shuffle and not part.shuffle:
            # honor shuffle=True even when an (unshuffled) partitioning was injected
            part = Partitioning(part.replicas, shuffle=True, seed=self.seed)
        return part.generate(len(self._entries), self.epoch)

    def _padding_value(self, name: str):
        return self._schema[name].padding_value

    def _make_batch(self, chunk: np.ndarray, L: int, dtypes: Dict) -> Batch:
        with stage("batch_build", tracer=self.tracer, python_rows=self._python_rows):
            return self._assemble_batch(chunk, L, dtypes)

    def _assemble_batch(self, chunk: np.ndarray, L: int, dtypes: Dict) -> Batch:
        n_real = len(chunk)
        if n_real < self.batch_size:  # pad final batch by repeating its first row
            chunk = np.concatenate(
                [chunk, np.full(self.batch_size - n_real, chunk[0], dtype=chunk.dtype)]
            )
        batch: Batch = {}
        spans = self._entries[chunk]  # [B, 3] (row, start, stop)
        for name in self._seq_names:
            pad = self._padding_value(name)
            if name in self._flat:
                from replay_tpu.native import gather_pad_spans

                flat, offsets = self._flat[name]
                # a secondary feature may be shorter than the item sequence
                # that defined the window: clamp to ITS row length (the same
                # silent-truncation semantics as python slicing)
                row_len = offsets[spans[:, 0] + 1] - offsets[spans[:, 0]]
                stops = np.minimum(spans[:, 2], row_len)
                starts = np.minimum(spans[:, 1], stops)
                arr, mask = gather_pad_spans(
                    flat, offsets, spans[:, 0], starts, stops, L, pad
                )
                batch[name] = arr.astype(dtypes[name], copy=False)
            else:
                arr = np.full((self.batch_size, L), pad, dtype=dtypes[name])
                mask = np.zeros((self.batch_size, L), dtype=bool)
                for b, (row, start, stop) in enumerate(spans.tolist()):
                    seq = self.dataset.get_sequence(row, name)[start:stop]
                    seq = seq[-L:]
                    arr[b, L - len(seq) :] = seq
                    mask[b, L - len(seq) :] = True
                batch[name] = arr
            batch[f"{name}_mask"] = np.asarray(mask, bool)
        for name, column in self._scalars.items():
            batch[name] = _take(column, spans[:, 0])
        batch["query_id"] = _take(self._query_ids, spans[:, 0])
        valid = np.zeros(self.batch_size, dtype=bool)
        valid[:n_real] = True
        batch["valid"] = valid
        return batch

    def __iter__(self) -> Iterator[Batch]:
        order, dtypes = self._entry_order(), self._dtypes
        if not self.bucket_boundaries:
            L = self.max_sequence_length
            for chunk_start in range(0, len(order), self.batch_size):
                yield self._make_batch(order[chunk_start : chunk_start + self.batch_size], L, dtypes)
            return
        # length-bucketed: every batch pads only to its bucket's length
        boundaries = self._buckets()
        bucket_ids = self._bucket_ids(self._entries[order], boundaries)
        queues: Dict[int, list] = {bucket: [] for bucket in boundaries}
        for entry, bucket_id in zip(order, bucket_ids):
            bucket = boundaries[bucket_id]
            queues[bucket].append(entry)
            if len(queues[bucket]) == self.batch_size:
                yield self._make_batch(np.asarray(queues[bucket]), bucket, dtypes)
                queues[bucket] = []
        for bucket in boundaries:  # flush short tails (padded + valid-masked)
            if queues[bucket]:
                yield self._make_batch(np.asarray(queues[bucket]), bucket, dtypes)


class TransformedBatches:
    """Re-iterable transform view over a batcher that FORWARDS the streaming
    protocol (``set_epoch`` / ``supports_cursor`` / ``cursor_for`` /
    ``restore_cursor`` / ``scan_compatible``).

    ``Trainer.fit`` duck-types its batch source: a bare generator applying a
    transform pipeline would hide the underlying batcher's resumable cursor
    (and its epoch hook), silently downgrading out-of-core resume to
    fast-forwarding. Wrap the pipeline here instead::

        fit(TransformedBatches(batcher, Compose(pipeline)), ...)

    The transform must be a deterministic ``batch -> batch`` callable — the
    cursor contract re-applies it to the same raw batches after a resume.
    """

    def __init__(self, source, transform) -> None:
        self.source = source
        self.transform = transform

    def __iter__(self):
        for batch in self.source:
            yield self.transform(batch)

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.source, "set_epoch"):
            self.source.set_epoch(epoch)

    @property
    def supports_cursor(self) -> bool:
        return bool(getattr(self.source, "supports_cursor", False))

    def cursor_for(self, batches_emitted: int):
        return self.source.cursor_for(batches_emitted)

    def restore_cursor(self, cursor) -> None:
        self.source.restore_cursor(cursor)

    @property
    def scan_compatible(self) -> bool:
        return bool(getattr(self.source, "scan_compatible", True))


def validation_batches(
    train: SequentialDataset,
    ground_truth: SequentialDataset,
    batch_size: int,
    max_sequence_length: int,
    partitioning: Optional[Partitioning] = None,
) -> Iterator[Batch]:
    """Batches for Trainer.validate: input histories from ``train`` plus padded
    ``ground_truth``/``train`` id sets (−1 padding, MetricsBuilder's contract).

    Mirrors the reference validation dataset (torch_sequential_dataset.py:184):
    only queries present in both splits are evaluated.
    """
    train_common, gt_common = SequentialDataset.keep_common_query_ids(train, ground_truth)
    item_col = train_common.item_id_column
    gt_max = max((gt_common.get_sequence_length(i) for i in range(len(gt_common))), default=1)
    train_max = max(
        (train_common.get_sequence_length(i) for i in range(len(train_common))), default=1
    )
    batcher = SequenceBatcher(
        train_common,
        batch_size=batch_size,
        max_sequence_length=max_sequence_length,
        windows=False,
        partitioning=partitioning,
    )
    for batch in batcher:
        n = len(batch["query_id"])
        gt = np.full((n, gt_max), DEFAULT_GROUND_TRUTH_PADDING_VALUE, dtype=np.int64)
        seen = np.full((n, train_max), DEFAULT_TRAIN_PADDING_VALUE, dtype=np.int64)
        for b, query_id in enumerate(batch["query_id"]):
            if not batch["valid"][b]:
                continue
            gt_seq = gt_common.get_sequence_by_query_id(query_id, item_col)
            gt[b, : len(gt_seq)] = gt_seq
            seen_seq = train_common.get_sequence_by_query_id(query_id, item_col)
            seen[b, : len(seen_seq)] = seen_seq
        batch["ground_truth"] = gt
        batch["train"] = seen
        yield batch
