"""Sequence packing: fit several short user sequences into one ``[B, L]`` row.

On real interaction data most sequences are far shorter than
``max_sequence_length``, so fixed-shape batches are mostly padding — the
accelerator-utilization killer "Demystifying BERT" (PAPERS.md) quantifies and
TurboGR treats as a first-class training lever. This module packs sequences
with first-fit length-bucketed bin packing:

* each entry's length is rounded UP to the smallest bucket boundary holding
  it (buckets quantize the slot widths, keeping the packing deterministic and
  cache-friendly; no boundaries = exact lengths);
* entries are placed first-fit in stream order into open rows of capacity
  ``max_sequence_length`` (bounded open-row window, so packing streams);
* every packed row carries ``segment_ids`` — ``0`` on padding, ``1..k`` per
  packed sequence — which the models' attention path turns into a
  block-diagonal mask (no cross-sequence attention) and the packed transform
  template turns into a cross-segment label mask (no cross-sequence loss).
  See docs/performance.md "Feeding the beast" for the correctness argument.

The non-packing fallback for length-skewed data remains
``SequenceBatcher(bucket_boundaries=...)`` (length-bucketed batches, one
compiled program per width — single-host only); packing keeps ONE ``[B, L]``
shape, so it composes with the scan-chunked fit and multi-host partitioning.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from replay_tpu.data.nn.iterator import Batch, SequenceBatcher
from replay_tpu.obs.trace import stage


def bucketed_length(length: int, capacity: int, boundaries: Optional[Sequence[int]]) -> int:
    """``length`` rounded up to the smallest bucket boundary holding it
    (boundaries above ``capacity`` are ignored; no boundaries = exact)."""
    length = min(length, capacity)
    if not boundaries:
        return length
    for bound in sorted(b for b in set(boundaries) if b < capacity):
        if length <= bound:
            return bound
    return capacity


def first_fit_pack(
    lengths: Sequence[int],
    capacity: int,
    bucket_boundaries: Optional[Sequence[int]] = None,
    open_rows: int = 64,
) -> List[List[int]]:
    """First-fit bin packing of entry indices into rows of ``capacity`` slots.

    Deterministic in input order: each entry goes to the FIRST open row with
    room for its (bucket-rounded) length; at most ``open_rows`` rows stay
    open (a bounded window, so the packer streams — a row that no plausible
    entry fits into closes in arrival order). Returns the packed rows, each a
    list of entry indices in placement order.
    """
    if capacity < 1:
        msg = "capacity must be >= 1"
        raise ValueError(msg)
    # normalize the boundaries ONCE (bucketed_length would re-sort per entry)
    bounds = sorted(b for b in set(bucket_boundaries or ()) if b < capacity)
    closed: List[List[int]] = []
    open_bins: List[Tuple[int, List[int]]] = []  # (free slots, entry indices)
    for index, raw in enumerate(lengths):
        need = min(int(raw), capacity)
        if bounds:  # round up to the smallest holding bucket, else capacity
            need = next((b for b in bounds if need <= b), capacity)
        if need < 1:
            need = 1
        placed = False
        for slot, (free, members) in enumerate(open_bins):
            if need <= free:
                members.append(index)
                open_bins[slot] = (free - need, members)
                placed = True
                break
        if not placed:
            open_bins.append((capacity - need, [index]))
            if len(open_bins) > open_rows:
                free, members = open_bins.pop(0)
                closed.append(members)
    closed.extend(members for _, members in open_bins)
    return closed


@dataclass
class PackedSequenceBatcher(SequenceBatcher):
    """A :class:`SequenceBatcher` that packs several sequences per row.

    Emits fixed ``[batch_size, max_sequence_length]`` batches where each row
    holds up to ``max_segments`` LEFT-ALIGNED sequences back to back:
    ``{feature: [B, L], feature_mask: [B, L], segment_ids: [B, L], valid: [B]}``.
    ``segment_ids`` is 0 on padding and ``1..k`` per packed sequence; the
    per-feature masks are True exactly where ``segment_ids > 0``.

    Feed the output through
    :func:`~replay_tpu.nn.transform.template.make_packed_sasrec_transforms`
    (next-token shift + cross-segment label masking) into a model whose
    attention path takes ``segment_ids`` (SasRec/Bert4Rec bodies) — attention
    and loss then never cross a segment boundary. Scan-compatible: ONE
    compiled shape for the whole epoch.

    ``bucket_boundaries`` here selects the packing slot quantization (the
    length-bucketed part of first-fit), NOT per-batch widths — every batch
    stays ``[B, L]``, so the multi-replica partitioning seam keeps working.
    """

    max_segments: int = 0  # 0 = unlimited
    open_rows: int = 64

    def __post_init__(self) -> None:
        # bypass SequenceBatcher's bucketed-width validation: packing reuses
        # bucket_boundaries as slot quantization while every batch keeps ONE
        # shape, so multi-replica partitioning stays sound
        boundaries, self.bucket_boundaries = self.bucket_boundaries, None
        super().__post_init__()
        self.bucket_boundaries = boundaries
        if self.windows:
            # windows already slice long sequences to <= L; packing composes,
            # but window entries of exactly L never pack — allowed, just noted
            pass

    @property
    def scan_compatible(self) -> bool:  # type: ignore[override]
        """Packed batches all share one ``[B, L]`` shape (the packing rounds
        SLOTS, not batch widths), so the scan-chunked fit accepts them."""
        return True

    def _packed_rows(self, order: np.ndarray) -> List[List[int]]:
        # the packing is a pure function of the (epoch-keyed) entry order:
        # cache it so len() + iteration + packing_summary() pack once
        cache_key = (self.epoch, self.shuffle, self.seed, len(order))
        cached = getattr(self, "_pack_cache", None)
        if cached is not None and cached[0] == cache_key:
            return cached[1]
        entries = self._entries[order]
        lengths = np.minimum(entries[:, 2] - entries[:, 1], self.max_sequence_length)
        rows = first_fit_pack(
            lengths.tolist(),
            self.max_sequence_length,
            self.bucket_boundaries,
            open_rows=self.open_rows,
        )
        if self.max_segments:
            bounded: List[List[int]] = []
            for members in rows:
                for start in range(0, len(members), self.max_segments):
                    bounded.append(members[start : start + self.max_segments])
            rows = bounded
        # map positions-in-order back to entry ids
        rows = [[int(order[i]) for i in members] for members in rows]
        self._pack_cache = (cache_key, rows)
        return rows

    def __len__(self) -> int:  # type: ignore[override]
        from replay_tpu.data.batching import uniform_batch_count

        rows = self._packed_rows(self._entry_order())
        return uniform_batch_count(len(rows), self.batch_size)

    def _assemble_packed(
        self, rows: List[List[int]], dtypes: Dict
    ) -> Batch:
        L = self.max_sequence_length
        B = self.batch_size
        n_real = len(rows)
        batch: Batch = {}
        segment_ids = np.zeros((B, L), np.int32)
        slots: List[List[Tuple[int, int, int, int, int]]] = []
        for b, members in enumerate(rows):
            offset = 0
            row_slots = []
            for seg, entry in enumerate(members, start=1):
                row, start, stop = self._entries[entry].tolist()
                raw_len = stop - start
                take = min(raw_len, L)
                # recency truncation like the unpacked batcher: keep the LAST
                # `take` events of the window
                seg_start = start + (raw_len - take)
                slot_width = bucketed_length(take, L, self.bucket_boundaries)
                if offset + take > L:
                    # first-fit guaranteed bucketed widths fit; real length
                    # can't exceed its bucket
                    msg = f"packed row overflow: offset {offset} + {take} > {L}"
                    raise RuntimeError(msg)
                segment_ids[b, offset : offset + take] = seg
                row_slots.append((row, seg_start, stop, offset, take))
                offset += slot_width
            slots.append(row_slots)
        for name in self._seq_names:
            pad = self._padding_value(name)
            arr = np.full((B, L), pad, dtype=dtypes[name])
            for b, row_slots in enumerate(slots):
                for row, seg_start, stop, offset, take in row_slots:
                    seq = np.asarray(self.dataset.get_sequence(row, name)).reshape(-1)
                    # secondary features may be shorter than the item sequence
                    # that defined the window: clamp like the unpacked path
                    seg = seq[min(seg_start, len(seq)) : min(stop, len(seq))]
                    seg = seg[-take:]
                    arr[b, offset : offset + len(seg)] = seg
            batch[name] = arr
            batch[f"{name}_mask"] = segment_ids > 0
        for name in self._scalar_names:
            # a packed row holds SEVERAL queries: scalar features are not
            # representable per row — take the FIRST segment's value (masked
            # consumers should not rely on scalars under packing)
            values = [
                np.asarray(self.dataset.get_sequence(row_slots[0][0], name)).reshape(-1)[0]
                for row_slots in slots
                if row_slots
            ]
            column = np.asarray(values) if values else np.zeros(0, np.int64)
            if len(column) < B:  # pad the final short batch to the fixed shape
                fill = column[:1] if len(column) else np.zeros(1, column.dtype)
                column = np.concatenate([column, np.repeat(fill, B - len(column))])
            batch[name] = column
        batch["segment_ids"] = segment_ids
        valid = np.zeros(B, bool)
        valid[:n_real] = True
        batch["valid"] = valid
        return batch

    def __iter__(self) -> Iterator[Batch]:  # type: ignore[override]
        order, dtypes = self._entry_order(), self._dtypes
        rows = self._packed_rows(order)
        for start in range(0, len(rows), self.batch_size):
            chunk = rows[start : start + self.batch_size]
            # every packed row is assembled in python loops, and says so
            with stage("batch_build", tracer=self.tracer, python_rows=len(chunk)):
                batch = self._assemble_packed(chunk, dtypes)
            yield batch

    # -- padding accounting -------------------------------------------------- #
    def packing_summary(self) -> Dict[str, float]:
        """Epoch-level packing stats: ``padding_fraction`` (fraction of the
        ``[B, L]`` token grid that is padding), ``rows`` (packed rows),
        ``segments_per_row`` and the unpacked baseline's padding fraction for
        the same entries."""
        order = self._entry_order()
        entries = self._entries[order]
        lengths = np.minimum(entries[:, 2] - entries[:, 1], self.max_sequence_length)
        rows = self._packed_rows(order)
        from replay_tpu.data.batching import uniform_batch_count

        n_batches = uniform_batch_count(len(rows), self.batch_size)
        grid = n_batches * self.batch_size * self.max_sequence_length
        real = int(lengths.sum())
        unpacked_batches = uniform_batch_count(len(entries), self.batch_size)
        unpacked_grid = unpacked_batches * self.batch_size * self.max_sequence_length
        return {
            "rows": float(len(rows)),
            "segments_per_row": float(len(entries)) / max(len(rows), 1),
            "padding_fraction": 1.0 - real / grid if grid else 0.0,
            "unpacked_padding_fraction": (
                1.0 - real / unpacked_grid if unpacked_grid else 0.0
            ),
        }
