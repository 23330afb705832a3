"""``SequenceBatcher`` builds its batches from arrays made once, and the batches
are the ones the per-row construction gave.

The plain reference below IS that construction (one ``get_sequence`` /
``get_query_id`` a row, python slicing, a list of windows a row): every leaf of
every batch must equal it in value, dtype and shape. numpy only: no jax.
"""

from typing import List, Optional, Tuple

import numpy as np
import pandas as pd
import pytest

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import (
    PackedSequenceBatcher,
    SequenceBatcher,
    SequentialDataset,
    TensorFeatureInfo,
    TensorSchema,
)
from replay_tpu.obs.trace import claim_chunk

NUM_ITEMS = 23
# lengths around the windows' and the buckets' edges: empty, 1, L-1, L, L+1, 2L, 2L+1 ...
LENGTHS = (0, 1, 3, 4, 5, 7, 8, 9, 12, 16, 17, 2, 8, 30, 6, 11, 8)
MAX_LEN = 8

QUERY_IDS = {
    "int": lambda n: np.arange(100, 100 + n),
    "str": lambda n: np.asarray([f"user-{i * i}" for i in range(n)], dtype=object),
    "float": lambda n: np.arange(n) / 4.0,
}


def make_dataset(features: str, query_ids: str = "int") -> SequentialDataset:
    """``features``: ``item`` (the item sequence alone), ``rich`` (plus a float
    sequence SHORTER than the item one on some rows, an int and a float32 scalar),
    ``exotic`` (plus a bool sequence, which the native gather does not take)."""
    rng = np.random.default_rng(5)
    infos = [
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=NUM_ITEMS)
    ]
    frame = {
        "query_id": QUERY_IDS[query_ids](len(LENGTHS)),
        "item_id": [rng.integers(0, NUM_ITEMS, n) for n in LENGTHS],
    }
    if features in ("rich", "exotic"):
        infos += [
            TensorFeatureInfo("price", FeatureType.NUMERICAL, is_seq=True, tensor_dim=1),
            TensorFeatureInfo("age", FeatureType.CATEGORICAL, cardinality=90),
            TensorFeatureInfo("weight", FeatureType.NUMERICAL, tensor_dim=1),
        ]
        # every third row holds fewer prices than items
        frame["price"] = [
            rng.random(n if row % 3 else n // 2) for row, n in enumerate(LENGTHS)
        ]
        frame["age"] = rng.integers(0, 90, len(LENGTHS))
        frame["weight"] = rng.random(len(LENGTHS)).astype(np.float32)
    if features == "exotic":
        infos.append(TensorFeatureInfo("seen", FeatureType.NUMERICAL, is_seq=True, tensor_dim=1))
        frame["seen"] = [rng.random(n) < 0.5 for n in LENGTHS]
    return SequentialDataset(TensorSchema(infos), "query_id", "item_id", pd.DataFrame(frame))


# --------------------------------------------------------------------------- #
# the plain reference: the per-row construction
# --------------------------------------------------------------------------- #
def reference_windows(length: int, max_len: int, stride: Optional[int]) -> List[Tuple[int, int]]:
    if length <= max_len:
        return [(0, length)]
    stride = stride or max_len
    stops = list(range(max_len, length, stride)) + [length]
    return [(stop - max_len, stop) for stop in stops]


def reference_index(dataset, max_len, windows, stride) -> List[Tuple[int, int, int]]:
    index = []
    for row in range(len(dataset)):
        length = dataset.get_sequence_length(row)
        spans = (
            reference_windows(length, max_len, stride)
            if windows
            else [(max(0, length - max_len), length)]
        )
        index.extend((row, start, stop) for start, stop in spans)
    return index


def reference_batch(dataset, index, chunk, batch_size, width):
    n_real = len(chunk)
    chunk = list(chunk) + [chunk[0]] * (batch_size - n_real)
    batch = {}
    features = dataset.schema.all_features
    for feature in (f for f in features if f.is_seq):
        name = feature.name
        first = np.asarray(dataset.get_sequence(0, name))
        dtype = np.int32 if np.issubdtype(first.dtype, np.integer) else np.float32
        arr = np.full((batch_size, width), feature.padding_value, dtype=dtype)
        mask = np.zeros((batch_size, width), dtype=bool)
        for b, entry in enumerate(chunk):
            row, start, stop = index[entry]
            seq = dataset.get_sequence(row, name)[start:stop][-width:]
            arr[b, width - len(seq):] = seq
            mask[b, width - len(seq):] = True
        batch[name], batch[f"{name}_mask"] = arr, mask
    for name in (f.name for f in features if not f.is_seq):
        batch[name] = np.asarray(
            [np.asarray(dataset.get_sequence(index[e][0], name)).reshape(-1)[0] for e in chunk]
        )
    batch["query_id"] = np.asarray([dataset.get_query_id(index[e][0]) for e in chunk])
    batch["valid"] = np.arange(batch_size) < n_real
    return batch


def reference_batches(dataset, batcher):
    """The batcher's epoch the per-row way, in its entry order (the
    partitioning's, unchanged) and with its bucket queues."""
    index = reference_index(dataset, batcher.max_sequence_length, batcher.windows,
                            batcher.window_stride)
    order = [int(entry) for entry in batcher._entry_order()]
    size, top = batcher.batch_size, batcher.max_sequence_length
    if not batcher.bucket_boundaries:
        return [
            reference_batch(dataset, index, order[at:at + size], size, top)
            for at in range(0, len(order), size)
        ]
    boundaries = sorted(b for b in set(batcher.bucket_boundaries) if b < top) + [top]
    queues = {bucket: [] for bucket in boundaries}
    batches = []
    for entry in order:
        length = min(index[entry][2] - index[entry][1], top)
        bucket = next(b for b in boundaries if length <= b)
        queues[bucket].append(entry)
        if len(queues[bucket]) == size:
            batches.append(reference_batch(dataset, index, queues[bucket], size, bucket))
            queues[bucket] = []
    for bucket in boundaries:
        if queues[bucket]:
            batches.append(reference_batch(dataset, index, queues[bucket], size, bucket))
    return batches


def assert_same_batches(got, expected):
    assert len(got) == len(expected)
    for batch, reference in zip(got, expected):
        assert list(batch) == list(reference)  # the leaves and their order
        for name, leaf in batch.items():
            assert type(leaf) is np.ndarray, name
            assert leaf.dtype == reference[name].dtype, name
            assert leaf.shape == reference[name].shape, name
            np.testing.assert_array_equal(leaf, reference[name], err_msg=name)


# --------------------------------------------------------------------------- #
# equality with the per-row construction
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("buckets", [None, (3, 5)], ids=["one_width", "bucketed"])
@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
@pytest.mark.parametrize(
    "windows,stride", [(False, None), (True, None), (True, 3)],
    ids=["last_events", "windows", "windows_stride3"],
)
@pytest.mark.parametrize("features", ["item", "rich", "exotic"])
def test_batches_equal_the_per_row_construction(features, windows, stride, shuffle, buckets):
    dataset = make_dataset(features)
    # 6 divides none of the 17, 26 and 35 entries: the last batch is short
    batcher = SequenceBatcher(
        dataset, batch_size=6, max_sequence_length=MAX_LEN, windows=windows,
        window_stride=stride, shuffle=shuffle, seed=3, bucket_boundaries=buckets,
    )
    expected = reference_batches(dataset, batcher)
    got = list(batcher)
    assert_same_batches(got, expected)
    assert len(batcher) == len(expected)
    assert not got[-1]["valid"].all()  # the padded last batch was among them
    batcher.set_epoch(1)  # another order, the same construction
    assert_same_batches(list(batcher), reference_batches(dataset, batcher))


@pytest.mark.parametrize("query_ids", ["str", "float"])
@pytest.mark.parametrize("windows", [False, True])
def test_a_query_id_that_is_no_integer_keeps_its_dtype(query_ids, windows):
    dataset = make_dataset("rich", query_ids)
    batcher = SequenceBatcher(dataset, batch_size=4, max_sequence_length=MAX_LEN,
                              windows=windows, shuffle=True, seed=1)
    got = list(batcher)
    assert_same_batches(got, reference_batches(dataset, batcher))
    # a batch of string ids is as wide as ITS longest id, as a list of them was
    kinds = {batch["query_id"].dtype.kind for batch in got}
    assert kinds == ({"U"} if query_ids == "str" else {"f"})
    if query_ids == "str":
        assert len({batch["query_id"].dtype for batch in got}) > 1


@pytest.mark.parametrize("windows,stride", [(False, None), (True, None), (True, 1), (True, 5)])
def test_the_index_is_the_windows_of_every_row(windows, stride):
    dataset = make_dataset("item")
    batcher = SequenceBatcher(dataset, batch_size=4, max_sequence_length=MAX_LEN,
                              windows=windows, window_stride=stride)
    expected = reference_index(dataset, MAX_LEN, windows, stride)
    assert batcher._entries.dtype == np.int64 and batcher._entries.shape == (len(expected), 3)
    assert batcher._entries.tolist() == [list(entry) for entry in expected]


def test_an_empty_dataset_has_no_entries_and_no_batches():
    dataset = make_dataset("rich")
    empty = dataset.filter_by_query_id([])
    batcher = SequenceBatcher(empty, batch_size=4, max_sequence_length=MAX_LEN, windows=True)
    assert batcher._entries.shape == (0, 3) and len(batcher) == 0 and list(batcher) == []


# --------------------------------------------------------------------------- #
# what assembles a batch: arrays, or the per-row loop that says so
# --------------------------------------------------------------------------- #
PER_ROW_LOOKUPS = ("get_sequence", "get_query_id", "get_sequence_length")


class CountingDataset:
    """A dataset that counts the per-row lookups made through it."""

    def __init__(self, dataset):
        self._dataset = dataset
        self.calls = dict.fromkeys(PER_ROW_LOOKUPS, 0)

    def __len__(self):
        return len(self._dataset)

    def __getattr__(self, name):
        attribute = getattr(self._dataset, name)
        if name not in PER_ROW_LOOKUPS:
            return attribute

        def counted(*args):
            self.calls[name] += 1
            return attribute(*args)

        return counted


@pytest.mark.parametrize("buckets", [None, (3, 5)], ids=["one_width", "bucketed"])
@pytest.mark.parametrize("windows", [False, True])
def test_no_per_row_lookup_once_the_flat_layout_covers_the_schema(windows, buckets):
    dataset = CountingDataset(make_dataset("rich", "str"))
    batcher = SequenceBatcher(dataset, batch_size=4, max_sequence_length=MAX_LEN, windows=windows,
                              shuffle=True, bucket_boundaries=buckets)
    assert set(batcher._flat) == {"item_id", "price"}
    for _ in range(2):
        assert len(list(batcher)) == len(batcher)
    # neither building the batcher nor two epochs of batches asked for a row
    assert dataset.calls == dict.fromkeys(PER_ROW_LOOKUPS, 0)


def python_rows_of(batcher) -> Tuple[int, int]:
    """(batches, ``batch_build_python_rows``) of one epoch, from this thread's
    stage totals: what the chunk stage log's record takes."""
    claim_chunk(-1)
    batches = len(list(batcher))
    return batches, claim_chunk(-2).get("batch_build_python_rows")


@pytest.mark.parametrize("features,per_batch", [("item", 0), ("rich", 0), ("exotic", 4)])
def test_batch_build_counts_the_rows_of_the_python_loop(features, per_batch):
    dataset = CountingDataset(make_dataset(features))
    batcher = SequenceBatcher(dataset, batch_size=4, max_sequence_length=MAX_LEN, windows=True)
    batches, python_rows = python_rows_of(batcher)
    assert batches > 1 and python_rows == batches * per_batch
    # the lookups are the loop's: one a row and exotic feature, none otherwise
    assert dataset.calls["get_sequence"] == python_rows and dataset.calls["get_query_id"] == 0


def test_the_packed_batcher_reports_its_rows_and_reads_the_same_index():
    dataset = make_dataset("rich")
    plain = SequenceBatcher(dataset, batch_size=4, max_sequence_length=MAX_LEN, windows=True)
    packed = PackedSequenceBatcher(dataset, batch_size=4, max_sequence_length=MAX_LEN, windows=True)
    assert packed._entries.tolist() == plain._entries.tolist()
    claim_chunk(-1)
    batches = list(packed)
    # every packed row is assembled in python loops, and the counter says so
    assert claim_chunk(-2)["batch_build_python_rows"] == sum(int(b["valid"].sum()) for b in batches)
    # every event of every entry is in exactly one segment
    events = sum(int((b["segment_ids"][b["valid"]] > 0).sum()) for b in batches)
    assert events == int(np.minimum(plain._entries[:, 2] - plain._entries[:, 1], MAX_LEN).sum())
