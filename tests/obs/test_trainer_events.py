"""Trainer.fit event emission.

The smoke test is the acceptance gate for the obs subsystem: two epochs of a
tiny SASRec through ``fit`` with a ``JsonlLogger`` must produce the full event
sequence with finite loss/throughput and exactly ONE train-step compile across
both epochs (the static-shapes invariant, now observable). The scan-chunked,
device-fed fit (the path every benchmark cell runs) must end on an
``on_fit_end`` that carries the same observability fields.
"""

import json
import os

import numpy as np
import pytest

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.obs import JsonlLogger

NUM_ITEMS = 12
SEQ_LEN = 8
BATCH = 8  # divisible by the 8-device data axis


def _run_dir(tmp_path, name):
    """CI exports REPLAY_TPU_RUN_DIR so the smoke run's telemetry ships as a
    workflow artifact; locally the run log lands in tmp_path."""
    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    return os.path.join(base, name) if base else str(tmp_path / name)


def _make_batch(rng):
    items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN + 1)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    return {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }


@pytest.mark.jax
@pytest.mark.smoke
def test_fit_event_stream_single_compile(tmp_path):
    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            cardinality=NUM_ITEMS,
            embedding_dim=16,
        )
    )
    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1, num_heads=1,
                   max_sequence_length=SEQ_LEN)
    trainer = PROGRAMS.share_init(Trainer(  # its own programs: their count is asserted
        model=model,
        loss=CE(),
        optimizer=OptimizerFactory(name="adam", learning_rate=1e-2),
        mesh=make_mesh(),
    ))
    rng = np.random.default_rng(0)
    batches = [_make_batch(rng) for _ in range(3)]

    def val_batches():
        batch = dict(batches[0])
        batch["ground_truth"] = batches[0]["positive_labels"][:, -1, :].astype(np.int32)
        return [batch]

    # mode="w": REPLAY_TPU_RUN_DIR is a fixed path — a re-run in the same
    # workspace must not append a second event stream and fail the counts
    run_dir = _run_dir(tmp_path, "fit_smoke")
    with JsonlLogger(run_dir, mode="w") as sink:
        trainer.fit(
            lambda: iter(batches),
            epochs=2,
            loggers=sink,
            val_batches=val_batches,
            metrics=("ndcg",),
            top_k=(5,),
        )

    lines = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    names = [line["event"] for line in lines]
    assert names[0] == "on_fit_start" and names[-1] == "on_fit_end"
    assert names.count("on_validation_end") == 2 and names.count("on_epoch_end") == 2
    steps = [line for line in lines if line["event"] == "on_train_step"]
    assert len(steps) == 6  # 3 batches x 2 epochs, one event per step
    for record in steps:
        assert np.isfinite(record["loss"])
        assert np.isfinite(record["samples_per_sec"]) and record["samples_per_sec"] > 0
        assert record["lr"] == pytest.approx(1e-2)
    assert [s["step"] for s in steps] == list(range(1, 7))
    # the validation record reaches the stream with the epoch's metrics
    val = [line for line in lines if line["event"] == "on_validation_end"]
    assert all("ndcg@5" in line["record"] for line in val)
    # static-shapes invariant: ONE compiled train step across both epochs
    assert trainer.compile_tracker.traces["train_step"] == 1
    fit_end = lines[-1]
    assert fit_end["compile"]["train_step"]["traces"] == 1
    assert fit_end["telemetry"]["steps"] == 5  # 6 ticks - 1 warmup
    assert np.isfinite(fit_end["telemetry"]["samples_per_sec"])


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


class ListSink:
    """A sink that conforms to ``RunLogger`` by structure only (no subclass)
    and keeps the events it is handed."""

    def __init__(self):
        self.events = []

    def log_event(self, event):
        self.events.append(event)


def _small_trainer(optimizer=None) -> Trainer:
    """The d=8 model of the tests below; the trainers of the default optimizer
    share their two programs, another optimizer keeps its own."""
    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            cardinality=NUM_ITEMS,
            embedding_dim=8,
        )
    )
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, num_heads=1,
                   max_sequence_length=SEQ_LEN)
    trainer = Trainer(model=model, loss=CE(),
                      optimizer=optimizer or OptimizerFactory(learning_rate=1e-2),
                      mesh=make_mesh())
    return PROGRAMS.share_init(trainer) if optimizer else PROGRAMS.adopt(trainer)


@pytest.mark.jax
def test_fit_sparse_cadence_reports_finite_telemetry(caplog):
    """log_every-only path, fit shorter than 2x the cadence: the epoch-boundary
    flush + warmup proration must still produce real steady-state numbers in
    the fit-end summary (not an all-NaN telemetry block)."""
    import logging

    trainer = _small_trainer()
    rng = np.random.default_rng(1)
    batches = [_make_batch(rng) for _ in range(4)]
    with caplog.at_level(logging.INFO, logger="replay_tpu"):
        trainer.fit(lambda: iter(batches), epochs=1, log_every=3)
    fit_end = [r.getMessage() for r in caplog.records if "fit complete" in r.getMessage()]
    assert fit_end, caplog.records
    assert "'steps': 3.0" in fit_end[0]  # 4 steps - 1 warmup step (prorated)
    assert "nan" not in fit_end[0].split("'compile'")[0]  # telemetry is finite


@pytest.mark.jax
def test_fit_accepts_duck_typed_single_sink():
    """RunLogger is a protocol: a structurally-conforming sink that does not
    subclass it must be treated as ONE sink, not iterated as a sequence."""

    trainer = _small_trainer()
    rng = np.random.default_rng(3)
    duck = ListSink()
    trainer.fit(lambda: iter([_make_batch(rng), _make_batch(rng)]), epochs=1, loggers=duck)
    names = [e.event for e in duck.events]
    assert names[0] == "on_fit_start" and names[-1] == "on_fit_end"
    assert names.count("on_train_step") == 2


@pytest.mark.jax
def test_fit_lr_schedule_events_report_applied_rate(tmp_path):
    """The logged lr is the rate the optimizer applied: with linear warmup from
    0, the FIRST step's event must report 0.0 (optax indexes schedules by steps
    completed before the update)."""
    from replay_tpu.nn import LRSchedulerFactory

    trainer = _small_trainer(
        OptimizerFactory(
            learning_rate=1e-2,
            scheduler=LRSchedulerFactory(kind="warmup_linear", warmup_steps=4),
        )
    )
    rng = np.random.default_rng(2)
    batches = [_make_batch(rng) for _ in range(3)]
    run_dir = str(tmp_path / "lr_run")
    with JsonlLogger(run_dir) as sink:
        trainer.fit(lambda: iter(batches), epochs=1, loggers=sink)
    lines = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    lrs = [line["lr"] for line in lines if line["event"] == "on_train_step"]
    assert lrs[0] == pytest.approx(0.0)  # schedule(0), not schedule(1)
    assert lrs == sorted(lrs) and lrs[-1] > 0  # warming up


@pytest.mark.jax
def test_chunked_device_fed_fit_end_carries_obs_fields():
    """The benchmark's fit path (``scan_chunk=``, ``device_feed=True``) through
    the event stream: ONE scan program next to the per-step one, their compile
    wall time attributed (> 0) in ``on_fit_end``, the device-memory fields
    present (null on a backend without allocator stats, never absent) and a
    finite steady-state rate after the warm-up chunk."""
    trainer = _small_trainer(OptimizerFactory(name="sgd", learning_rate=1e-2))  # own programs
    rng = np.random.default_rng(5)
    batches = [_make_batch(rng) for _ in range(7)]  # K=2: three chunks + a per-step tail
    sink = ListSink()
    trainer.fit(batches, epochs=1, loggers=sink, scan_chunk=2, device_feed=True)

    steps = [e for e in sink.events if e.event == "on_train_step"]
    assert [e.step for e in steps] == list(range(1, 8))
    assert all(np.isfinite(e.payload["loss"]) for e in steps)
    fit_end = sink.events[-1]
    assert fit_end.event == "on_fit_end"
    compile_report = fit_end.payload["compile"]
    assert compile_report["train_scan"]["traces"] == 1
    assert compile_report["train_step"]["traces"] == 1
    assert compile_report["train_scan"]["compile_seconds"] > 0
    assert trainer.compile_tracker.total_compile_seconds > 0
    assert "peak_memory_bytes" in fit_end.payload  # null on the CPU, bytes on a TPU
    peak = fit_end.payload["peak_memory_bytes"]
    assert peak is None or peak > 0
    telemetry = fit_end.payload["telemetry"]
    assert np.isfinite(telemetry["samples_per_sec"]) and telemetry["samples_per_sec"] > 0
