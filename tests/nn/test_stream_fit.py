"""Out-of-core streaming fit: a parquet dataset bigger than the memory
budget trains through the chunked fit, mid-epoch SIGTERM + ``resume=True``
reproduces the uninterrupted run bit-for-bit by SEEKING the stream cursor
(no rescan), and the feed-efficiency/starvation telemetry lands in the run
artifact the CI ``stream_smoke`` job gates on.
"""

import json
import os
import signal

import numpy as np
import pandas as pd
import pytest

import jax

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import (
    ParquetBatcher,
    SequentialDataset,
    TensorFeatureInfo,
    TensorSchema,
    TransformedBatches,
    write_sequence_parquet,
)
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.nn.transform import Compose
from replay_tpu.nn.transform.template import make_default_sasrec_transforms
from replay_tpu.obs import JsonlLogger, SLORule, Tracer
from replay_tpu.utils.checkpoint import CheckpointManager

NUM_ITEMS = 30
SEQ_LEN = 7  # -> [B, 6] training batches
BATCH = 8
BUDGET_BYTES = 256  # smaller than a row group: forces out-of-core sub-slabs


def _run_dir(tmp_path, name):
    """CI exports REPLAY_TPU_RUN_DIR so the streaming smoke telemetry ships
    as a workflow artifact; locally the run log lands in tmp_path."""
    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    return os.path.join(base, name) if base else str(tmp_path / name)


def make_schema():
    return TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=NUM_ITEMS,
            embedding_dim=8,
        )
    )


@pytest.fixture(scope="module")
def stream_parquet(tmp_path_factory):
    schema = make_schema()
    rng = np.random.default_rng(0)
    n_rows = 61
    frame = pd.DataFrame(
        {
            "query_id": np.arange(n_rows),
            "item_id": [
                rng.integers(1, NUM_ITEMS, rng.integers(2, SEQ_LEN + 2)).astype(np.int64)
                for _ in range(n_rows)
            ],
        }
    )
    dataset = SequentialDataset(schema, "query_id", "item_id", frame)
    path = str(tmp_path_factory.mktemp("stream") / "seqs.parquet")
    write_sequence_parquet(path, dataset, rows_per_chunk=10)
    return path


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer():
    """A trainer of the module's one tiny model: each keeps its own state and
    history, all run the same two programs (traced and lowered once a module)."""
    schema = make_schema()
    model = SasRec(
        schema=schema, embedding_dim=8, num_blocks=1, num_heads=1,
        max_sequence_length=SEQ_LEN - 1, dropout_rate=0.0,
    )
    return PROGRAMS.adopt(
        Trainer(
            model=model, loss=CE(),
            optimizer=OptimizerFactory(learning_rate=1e-2),
            mesh=make_mesh(), seed=0,
        )
    )


def make_stream(path, **batcher_overrides):
    schema = make_schema()
    pipeline = Compose(make_default_sasrec_transforms(schema)["train"])
    kwargs = dict(
        source=path, batch_size=BATCH, shuffle=True, seed=0,
        shard="row_groups", memory_budget_bytes=BUDGET_BYTES, read_ahead=2,
        metadata={"item_id": {"shape": SEQ_LEN, "padding": 0}},
    )
    kwargs.update(batcher_overrides)
    batcher = ParquetBatcher(**kwargs)
    return batcher, TransformedBatches(
        batcher,
        lambda raw: pipeline(
            {
                "item_id": raw["item_id"],
                "item_id_mask": raw["item_id_mask"],
                "valid": raw["valid"],
            }
        ),
    )


class _SigtermAt:
    """Stream wrapper raising a REAL SIGTERM while batch ``at`` is fetched,
    forwarding the streaming protocol so the cursor machinery stays active."""

    def __init__(self, inner, at):
        self.inner = inner
        self.at = at
        self.position = 0
        self.raised = False

    def __iter__(self):
        for batch in self.inner:
            if self.position == self.at and not self.raised:
                self.raised = True
                signal.raise_signal(signal.SIGTERM)
            self.position += 1
            yield batch

    def set_epoch(self, epoch):
        self.inner.set_epoch(epoch)

    @property
    def supports_cursor(self):
        return self.inner.supports_cursor

    def cursor_for(self, k):
        return self.inner.cursor_for(k)

    def restore_cursor(self, cursor):
        self.inner.restore_cursor(cursor)

    @property
    def scan_compatible(self):
        return True


def assert_trees_equal(a, b):
    for left, right in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(left), np.asarray(right))


@pytest.mark.smoke
def test_out_of_core_dataset_exceeds_budget(stream_parquet):
    """The smoke dataset genuinely exceeds the memory budget: the epoch plan
    splits it into several bounded sub-slabs (out-of-core streaming)."""
    batcher, _ = make_stream(stream_parquet)
    batcher.set_epoch(0)
    slabs, _, _ = batcher._plan(0)
    total_bytes = os.path.getsize(stream_parquet)
    assert total_bytes > BUDGET_BYTES
    assert len(slabs) > 3
    unbudgeted, _, _ = make_stream(stream_parquet, memory_budget_bytes=None)[0]._plan(0)
    assert len(slabs) > len(unbudgeted)


@pytest.mark.smoke
def test_stream_fit_sigterm_resume_bit_for_bit(stream_parquet, tmp_path):
    """Acceptance: mid-epoch SIGTERM on the out-of-core chunked fit →
    position-stamped checkpoint WITH the stream cursor in the sidecar;
    ``resume=True`` seeks (slabs before the cursor are never re-read) and
    reproduces the uninterrupted run bit-for-bit — params, optimizer state,
    rng, step count and the final epoch's loss."""
    # uninterrupted reference: 2 epochs, scan-chunked + device-fed, with the
    # smoke artifact (events + trace + starvation SLO) for the CI job
    run_dir = _run_dir(tmp_path, "stream_smoke")
    trainer_a = make_trainer()
    _, stream_a = make_stream(stream_parquet)
    with JsonlLogger(run_dir, mode="w") as sink:
        state_a = trainer_a.fit(
            stream_a, epochs=2, scan_chunk=2, log_every=0, loggers=sink,
            tracer=True,
            # the device-feed path must keep I/O overlapped: starvation above
            # 90% of the stepping pipeline for 3 consecutive steps would fire
            slo_rules=[
                SLORule("replay_input_starvation", ">", 0.9, for_steps=3)
            ],
        )
    events = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    fit_end = [e for e in events if e.get("event") == "on_fit_end"][-1]
    assert 0.0 <= fit_end["input"]["padding_fraction"] < 1.0
    assert fit_end["input"]["tokens_real"] > 0
    assert not [e for e in events if e.get("event") == "on_slo_violation"]
    step_events = [e for e in events if e.get("event") == "on_train_step"]
    assert any("padding_fraction" in e for e in step_events)

    # preempted run: SIGTERM while batch 5 of epoch 0 is fetched
    trainer_b = make_trainer()
    manager = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=100)
    batcher_b, stream_b = make_stream(stream_parquet)
    sig = _SigtermAt(stream_b, at=5)
    state_mid = trainer_b.fit(
        sig, epochs=2, scan_chunk=2, log_every=0, checkpoint_manager=manager,
    )
    assert sig.raised
    assert int(state_mid.step) < int(state_a.step)
    meta = manager.metadata(manager.latest_step())
    assert meta["preempted"] and meta["mid_epoch"]
    cursor = meta["stream_cursor"]
    assert cursor["batches"] == meta["step_in_epoch"]

    # resume: the stream cursor seeks — count the slab reads to prove the
    # skipped prefix is never touched again
    trainer_c = make_trainer()
    batcher_c, stream_c = make_stream(stream_parquet)
    reads = []
    original = type(batcher_c)._read_slab

    def counting_read(self, path, slab):
        reads.append((slab.group, slab.start))
        return original(self, path, slab)

    batcher_c._read_slab = counting_read.__get__(batcher_c)
    state_c = trainer_c.fit(
        stream_c, epochs=2, scan_chunk=2, log_every=0,
        checkpoint_manager=manager, resume=True,
    )
    assert int(state_c.step) == int(state_a.step)
    assert_trees_equal(state_a.params, state_c.params)
    assert_trees_equal(state_a.opt_state, state_c.opt_state)
    np.testing.assert_array_equal(np.asarray(state_a.rng), np.asarray(state_c.rng))
    assert trainer_a.history[-1]["train_loss"] == trainer_c.history[-1]["train_loss"]
    total_slabs = len(batcher_c._plan(0)[0]) + len(batcher_c._plan(1)[0])
    skipped = int(cursor["slab"])
    assert skipped > 0  # the preemption landed past the first slab
    assert len(reads) <= total_slabs - skipped + 1


@pytest.mark.jax
def test_resume_without_cursor_falls_back_to_fast_forward(stream_parquet, tmp_path):
    """A sidecar without a stream cursor (older checkpoint, or a source that
    cannot seek) still resumes bit-for-bit through the consume-and-drop
    fast-forward path."""
    trainer_a = make_trainer()
    _, stream_a = make_stream(stream_parquet)
    state_a = trainer_a.fit(stream_a, epochs=2, scan_chunk=2, log_every=0)

    trainer_b = make_trainer()
    manager = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=100)
    _, stream_b = make_stream(stream_parquet)
    sig = _SigtermAt(stream_b, at=4)
    trainer_b.fit(
        sig, epochs=2, scan_chunk=2, log_every=0, checkpoint_manager=manager
    )
    step = manager.latest_step()
    meta = manager.metadata(step)
    assert "stream_cursor" in meta
    # strip the cursor, as an older-version checkpoint would look
    sidecar = manager._step_path(step).with_suffix(".json")
    stripped = {k: v for k, v in json.loads(sidecar.read_text()).items() if k != "stream_cursor"}
    sidecar.write_text(json.dumps(stripped))

    trainer_c = make_trainer()
    _, stream_c = make_stream(stream_parquet)
    state_c = trainer_c.fit(
        stream_c, epochs=2, scan_chunk=2, log_every=0,
        checkpoint_manager=manager, resume=True,
    )
    assert int(state_c.step) == int(state_a.step)
    assert_trees_equal(state_a.params, state_c.params)
    # the final (fully-measured) epoch's loss is bit-identical
    assert trainer_a.history[-1]["train_loss"] == trainer_c.history[-1]["train_loss"]


@pytest.mark.jax
def test_per_step_path_also_carries_cursor(stream_parquet, tmp_path):
    """The cursor contract holds on the un-chunked per-step fit too (the
    prefetch stage may read ahead of the executed step)."""
    trainer_a = make_trainer()
    _, stream_a = make_stream(stream_parquet)
    state_a = trainer_a.fit(stream_a, epochs=1, log_every=0, prefetch=2)

    trainer_b = make_trainer()
    manager = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=100)
    _, stream_b = make_stream(stream_parquet)
    sig = _SigtermAt(stream_b, at=3)
    trainer_b.fit(
        sig, epochs=1, log_every=0, prefetch=2, checkpoint_manager=manager
    )
    meta = manager.metadata(manager.latest_step())
    assert meta["stream_cursor"]["batches"] == meta["step_in_epoch"]

    trainer_c = make_trainer()
    _, stream_c = make_stream(stream_parquet)
    state_c = trainer_c.fit(
        stream_c, epochs=1, log_every=0, prefetch=2,
        checkpoint_manager=manager, resume=True,
    )
    assert int(state_c.step) == int(state_a.step)
    assert_trees_equal(state_a.params, state_c.params)


@pytest.mark.jax
def test_fit_reports_effective_tokens_in_step_events(stream_parquet):
    """Per-step events carry the feed-efficiency numbers and they are
    consistent with the batch shapes."""

    class Sink:
        def __init__(self):
            self.events = []

        def log_event(self, event):
            self.events.append(event)

    trainer = make_trainer()
    _, stream = make_stream(stream_parquet)
    sink = Sink()
    trainer.fit(stream, epochs=1, log_every=0, loggers=sink)
    steps = [e for e in sink.events if e.event == "on_train_step"]
    assert steps
    fractions = [
        e.payload["padding_fraction"]
        for e in steps
        if np.isfinite(e.payload.get("padding_fraction", float("nan")))
    ]
    assert fractions and all(0.0 <= f < 1.0 for f in fractions)
    fit_end = [e for e in sink.events if e.event == "on_fit_end"][-1]
    record = fit_end.payload["input"]
    assert record["tokens_grid"] % (BATCH * (SEQ_LEN - 1)) == 0
    assert 0 < record["tokens_real"] <= record["tokens_grid"]
