"""Property-based invariants (hypothesis) for the input pipeline — the
reference's fragmented-parquet strategy (SURVEY.md §4) applied to partitioning,
the fixed-shape batcher, and the native kernels."""

import numpy as np
import pandas as pd
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import (
    Partitioning,
    ReplicasInfo,
    SequenceBatcher,
    SequentialDataset,
    TensorFeatureInfo,
    TensorSchema,
)
from replay_tpu.native import gather_pad


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=200),
    num_replicas=st.integers(min_value=1, max_value=9),
    shuffle=st.booleans(),
    seed=st.integers(min_value=0, max_value=5),
)
def test_partitioning_invariants(n, num_replicas, shuffle, seed):
    shards = [
        Partitioning(ReplicasInfo(num_replicas, r), shuffle=shuffle, seed=seed).generate(n)
        for r in range(num_replicas)
    ]
    sizes = {len(s) for s in shards}
    assert len(sizes) == 1  # every replica sees the same number of rows
    union = np.concatenate(shards) if n else np.zeros(0)
    if n:
        assert set(union.tolist()) == set(range(n))  # exhaustive
        assert len(union) == -(-n // num_replicas) * num_replicas  # minimal padding
    else:
        assert len(union) == 0


@settings(max_examples=25, deadline=None)
@given(
    lengths=st.lists(st.integers(min_value=1, max_value=23), min_size=1, max_size=30),
    batch_size=st.integers(min_value=1, max_value=7),
    max_len=st.integers(min_value=2, max_value=9),
    windows=st.booleans(),
)
def test_batcher_invariants(lengths, batch_size, max_len, windows):
    schema = TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=1000)
    )
    frame = pd.DataFrame(
        {
            "query_id": np.arange(len(lengths)),
            # globally unique values so coverage is checkable
            "item_id": [
                np.arange(sum(lengths[:i]), sum(lengths[: i + 1])) for i in range(len(lengths))
            ],
        }
    )
    dataset = SequentialDataset(schema, "query_id", "item_id", frame)
    batcher = SequenceBatcher(dataset, batch_size=batch_size, max_sequence_length=max_len,
                              windows=windows)
    batches = list(batcher)
    assert len(batches) == len(batcher)
    seen_values = []
    for batch in batches:
        assert batch["item_id"].shape == (batch_size, max_len)
        assert batch["item_id_mask"].shape == (batch_size, max_len)
        valid_rows = batch["valid"]
        # masks are LEFT-padded: once True, stays True
        mask = batch["item_id_mask"][valid_rows]
        assert (np.diff(mask.astype(int), axis=1) >= 0).all()
        seen_values.append(batch["item_id"][valid_rows][mask])
    covered = set(np.concatenate(seen_values).tolist()) if seen_values else set()
    if windows:
        # window mode covers EVERY event of every sequence
        assert covered == set(range(sum(lengths)))
    else:
        # no-window mode covers exactly the last max_len events per sequence
        expected = set()
        for i, n in enumerate(lengths):
            start = sum(lengths[:i])
            expected.update(range(start + max(0, n - max_len), start + n))
        assert covered == expected


@settings(max_examples=40, deadline=None)
@given(
    row_lengths=st.lists(st.integers(min_value=0, max_value=12), min_size=1, max_size=10),
    max_len=st.integers(min_value=1, max_value=8),
    data=st.data(),
)
def test_gather_pad_matches_python_reference(row_lengths, max_len, data):
    values = np.arange(sum(row_lengths), dtype=np.int64)
    offsets = np.concatenate([[0], np.cumsum(row_lengths)]).astype(np.int64)
    indices = np.asarray(
        data.draw(
            st.lists(
                st.integers(min_value=0, max_value=len(row_lengths) - 1),
                min_size=1, max_size=8,
            )
        ),
        np.int64,
    )
    out, mask = gather_pad(values, offsets, indices, max_len, -1)
    for b, row in enumerate(indices):
        expected = values[offsets[row]: offsets[row + 1]][-max_len:]
        pad = max_len - len(expected)
        np.testing.assert_array_equal(out[b, pad:], expected)
        assert (out[b, :pad] == -1).all()
        assert mask[b].sum() == len(expected)

@settings(max_examples=40, deadline=None)
@given(
    row_lengths=st.lists(st.integers(min_value=0, max_value=9), min_size=1, max_size=8),
    max_len=st.integers(min_value=1, max_value=6),
    width=st.integers(min_value=1, max_value=4),
    floating=st.booleans(),
    data=st.data(),
)
def test_gather_pad_2d_matches_python_reference(row_lengths, max_len, width, floating, data):
    from replay_tpu.native import gather_pad_2d

    total = sum(row_lengths)
    values = np.arange(total * width, dtype=np.float64 if floating else np.int64).reshape(
        total, width
    )
    offsets = np.concatenate([[0], np.cumsum(row_lengths)]).astype(np.int64)
    indices = np.asarray(
        data.draw(
            st.lists(
                st.integers(min_value=0, max_value=len(row_lengths) - 1),
                min_size=1, max_size=6,
            )
        ),
        np.int64,
    )
    out, mask = gather_pad_2d(values, offsets, indices, max_len, width, -1)
    assert out.shape == (len(indices), max_len, width)
    assert out.dtype == values.dtype
    for b, row in enumerate(indices):
        expected = values[offsets[row]: offsets[row + 1]][-max_len:]
        pad = max_len - len(expected)
        np.testing.assert_array_equal(out[b, pad:], expected)
        assert (out[b, :pad] == -1).all()
        np.testing.assert_array_equal(mask[b], [False] * pad + [True] * len(expected))


def test_gather_pad_2d_rejects_bad_rows():
    from replay_tpu.native import gather_pad_2d

    values = np.arange(6, dtype=np.int64).reshape(3, 2)
    offsets = np.asarray([0, 1, 3], np.int64)
    with pytest.raises(ValueError):
        gather_pad_2d(values, offsets, np.asarray([5], np.int64), 4, 2, 0)


# --------------------------------------------------------------------------- #
# fragmented-parquet invariants (the reference's hypothesis strategy over
# random file sizes — tests/data/nn/parquet/test_parquet_dataset.py:12-49)
# --------------------------------------------------------------------------- #
def _write_fragments(root, file_rows, seq_width, start=0):
    """k parquet files with random row counts; globally unique scalar ids and
    fixed-width list rows derived from them (checkable coverage)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    next_id = start
    for i, n in enumerate(file_rows):
        ids = np.arange(next_id, next_id + n, dtype=np.int64)
        next_id += n
        table = pa.table(
            {
                "row_id": ids,
                "items": [
                    (np.arange(seq_width, dtype=np.int64) + rid).tolist() for rid in ids
                ],
            }
        )
        pq.write_table(table, f"{root}/part_{i}.parquet")
    return next_id - start


@settings(max_examples=20, deadline=None)
@given(
    file_rows=st.lists(st.integers(min_value=1, max_value=40), min_size=1, max_size=5),
    batch_size=st.integers(min_value=1, max_value=9),
    partition_size=st.integers(min_value=1, max_value=50),
    shuffle=st.booleans(),
)
def test_parquet_batcher_single_replica_exactness(
    file_rows, batch_size, partition_size, shuffle
):
    """Fixed shapes, ceil(n/B) batches, every written row delivered exactly once."""
    import tempfile

    from replay_tpu.data.nn import ParquetBatcher

    seq_width = 3
    with tempfile.TemporaryDirectory() as root:
        total = _write_fragments(root, file_rows, seq_width)
        batcher = ParquetBatcher(
            root, batch_size=batch_size,
            metadata={"items": {"shape": seq_width, "padding": -1}},
            partition_size=partition_size, shuffle=shuffle, seed=1,
        )
        batches = list(batcher)
        assert len(batches) == -(-total // batch_size)
        seen = []
        for batch in batches:
            assert batch["row_id"].shape == (batch_size,)
            assert batch["items"].shape == (batch_size, seq_width)
            assert batch["valid"].shape == (batch_size,)
            rows = batch["row_id"][batch["valid"]]
            np.testing.assert_array_equal(
                batch["items"][batch["valid"]],
                rows[:, None] + np.arange(seq_width)[None, :],
            )
            seen.append(rows)
        delivered = np.concatenate(seen)
        assert len(delivered) == total  # exactly once, no dupes, no drops
        assert set(delivered.tolist()) == set(range(total))


@settings(max_examples=15, deadline=None)
@given(
    file_rows=st.lists(st.integers(min_value=1, max_value=30), min_size=1, max_size=4),
    batch_size=st.integers(min_value=1, max_value=6),
    partition_size=st.integers(min_value=2, max_value=40),
    num_replicas=st.integers(min_value=2, max_value=4),
)
def test_parquet_batcher_replica_sharding_invariants(
    file_rows, batch_size, partition_size, num_replicas
):
    """Replicas emit identical batch counts (the collective-step invariant) and
    together cover every row; per-slab padding may duplicate, never drop."""
    import tempfile

    from replay_tpu.data.nn import ParquetBatcher, Partitioning, ReplicasInfo

    with tempfile.TemporaryDirectory() as root:
        total = _write_fragments(root, file_rows, seq_width=2)
        per_replica = []
        counts = []
        for r in range(num_replicas):
            batcher = ParquetBatcher(
                root, batch_size=batch_size,
                metadata={"items": {"shape": 2, "padding": -1}},
                partition_size=partition_size,
                partitioning=Partitioning(ReplicasInfo(num_replicas, r)),
            )
            batches = list(batcher)
            counts.append(len(batches))
            rows = [b["row_id"][b["valid"]] for b in batches]
            per_replica.append(np.concatenate(rows) if rows else np.zeros(0, np.int64))
            for b in batches:
                assert b["row_id"].shape == (batch_size,)
        assert len(set(counts)) == 1
        union = np.concatenate(per_replica)
        assert set(union.tolist()) == set(range(total))
        # padding duplicates at most (replicas - 1) rows per slab
        n_slabs = sum(-(-n // partition_size) for n in file_rows)
        assert len(union) - total <= (num_replicas - 1) * n_slabs


# --------------------------------------------------------------------------- #
# SequenceTokenizer -> SequenceBatcher path (VERDICT r4 weak #4): random logs
# through the full dataframe->tensor bridge
# --------------------------------------------------------------------------- #
def _random_log(seed: int, n_users: int, max_len: int):
    """String-keyed log with per-user shuffled timestamps and global row shuffle
    (exercises encoding AND the bridge's per-user timestamp sort)."""
    rng = np.random.default_rng(seed)
    rows = []
    for u in range(n_users):
        length = int(rng.integers(1, max_len + 1))
        times = rng.permutation(length)  # unsorted inside the user
        for t in times:
            rows.append((f"u{u}", f"i{rng.integers(0, 30)}", int(t)))
    frame = pd.DataFrame(rows, columns=["user_id", "item_id", "timestamp"])
    return frame.sample(frac=1.0, random_state=seed).reset_index(drop=True)


def _bridge(log):
    from replay_tpu.data import Dataset, FeatureHint, FeatureInfo, FeatureSchema
    from replay_tpu.data.nn import SequenceTokenizer, TensorFeatureSource
    from replay_tpu.data.schema import FeatureSource

    schema = FeatureSchema(
        [
            FeatureInfo("user_id", FeatureType.CATEGORICAL, FeatureHint.QUERY_ID),
            FeatureInfo("item_id", FeatureType.CATEGORICAL, FeatureHint.ITEM_ID),
            FeatureInfo("timestamp", FeatureType.NUMERICAL, FeatureHint.TIMESTAMP),
        ]
    )
    tensor_schema = TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            feature_sources=[TensorFeatureSource(FeatureSource.INTERACTIONS, "item_id")],
            embedding_dim=4,
        )
    )
    tokenizer = SequenceTokenizer(tensor_schema)
    sequential = tokenizer.fit_transform(Dataset(feature_schema=schema, interactions=log))
    item_map = tokenizer.item_id_encoder.mapping["item_id"]
    user_map = tokenizer.query_id_encoder.mapping["user_id"]
    expected = {}
    for user, group in log.groupby("user_id"):
        ordered = group.sort_values("timestamp", kind="stable")["item_id"]
        expected[user_map[user]] = [item_map[i] for i in ordered]
    return sequential, expected


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    n_users=st.integers(min_value=1, max_value=10),
    max_len=st.integers(min_value=1, max_value=14),
    batch_size=st.integers(min_value=1, max_value=5),
    seq_len=st.integers(min_value=1, max_value=12),
    shuffle=st.booleans(),
)
def test_tokenizer_batcher_last_window_roundtrip(
    seed, n_users, max_len, batch_size, seq_len, shuffle
):
    """windows=False (the predict path): each user appears exactly once across
    valid rows, left-padded with the padding id, and the unpadded row equals
    the LAST min(len, L) events of that user's time-ordered encoded history."""
    sequential, expected = _bridge(_random_log(seed, n_users, max_len))
    padding_id = sequential.schema["item_id"].padding_value
    batcher = SequenceBatcher(
        sequential, batch_size=batch_size, max_sequence_length=seq_len,
        windows=False, shuffle=shuffle, seed=seed,
    )
    seen_users = []
    for batch in batcher:
        assert batch["item_id"].shape == (batch_size, seq_len)
        assert batch["item_id_mask"].shape == (batch_size, seq_len)
        valid = batch.get("valid", np.ones(batch_size, bool))
        for b in np.flatnonzero(valid):
            mask = batch["item_id_mask"][b]
            row = batch["item_id"][b]
            assert (row[~mask] == padding_id).all()
            assert not mask[:-1][~mask[1:]].any()  # left padding: mask is a suffix
            user = int(batch["query_id"][b])
            seen_users.append(user)
            want = expected[user][-seq_len:]
            assert row[mask].tolist() == want
    assert sorted(seen_users) == sorted(expected)  # exactly once each


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    n_users=st.integers(min_value=1, max_value=8),
    max_len=st.integers(min_value=1, max_value=14),
    seq_len=st.integers(min_value=2, max_value=10),
)
def test_tokenizer_batcher_windows_cover_history(seed, n_users, max_len, seq_len):
    """windows=True (the train path): every window is a contiguous slice of the
    user's encoded history, no window exceeds L, and the union of windows
    covers every event of every user."""
    sequential, expected = _bridge(_random_log(seed, n_users, max_len))
    batcher = SequenceBatcher(
        sequential, batch_size=3, max_sequence_length=seq_len, windows=True,
    )
    covered = {user: np.zeros(len(seq), bool) for user, seq in expected.items()}
    for batch in batcher:
        valid = batch.get("valid", np.ones(len(batch["item_id"]), bool))
        for b in np.flatnonzero(valid):
            row = batch["item_id"][b][batch["item_id_mask"][b]].tolist()
            assert 0 < len(row) <= seq_len
            user = int(batch["query_id"][b])
            history = expected[user]
            # contiguous slice: find it and mark coverage. A window's content
            # may repeat in the history ([10, 1, 9, 11, 10, 1] with L=2), and
            # the content cannot say which occurrence the window was cut from:
            # mark every one (marking only the first left the later occurrence
            # uncovered although the windows tile the history)
            starts = [
                s for s in range(len(history) - len(row) + 1)
                if history[s : s + len(row)] == row
            ]
            assert starts, (row, history)
            for start in starts:
                covered[user][start : start + len(row)] = True
    for user, flags in covered.items():
        assert flags.all(), f"user {user} events not covered by any window"


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    n_users=st.integers(min_value=2, max_value=8),
    max_len=st.integers(min_value=1, max_value=14),
    boundary=st.integers(min_value=2, max_value=8),
)
def test_tokenizer_batcher_bucketing_preserves_content(seed, n_users, max_len, boundary):
    """Length bucketing changes only the padded WIDTH: every batch is padded to
    the smallest bucket covering its rows, and the multiset of unpadded rows
    equals the unbucketed batcher's."""
    seq_len = 10
    sequential, _ = _bridge(_random_log(seed, n_users, max_len))
    plain = SequenceBatcher(sequential, batch_size=2, max_sequence_length=seq_len)
    bucketed = SequenceBatcher(
        sequential, batch_size=2, max_sequence_length=seq_len,
        bucket_boundaries=(boundary,),
    )

    def rows(batcher, widths):
        out = []
        for batch in batcher:
            widths.append(batch["item_id"].shape[1])
            valid = batch.get("valid", np.ones(len(batch["item_id"]), bool))
            longest = 0
            for b in np.flatnonzero(valid):
                row = batch["item_id"][b][batch["item_id_mask"][b]]
                longest = max(longest, len(row))
                out.append((int(batch["query_id"][b]), tuple(row.tolist())))
            assert longest <= batch["item_id"].shape[1]
        return sorted(out)

    plain_widths, bucket_widths = [], []
    assert rows(plain, plain_widths) == rows(bucketed, bucket_widths)
    assert set(plain_widths) == {seq_len}
    assert set(bucket_widths) <= {min(boundary, seq_len), seq_len}
