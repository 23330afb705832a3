"""Scan-chunked ``Trainer.fit`` (docs/performance.md "Closing the dispatch gap").

``fit(scan_chunk=K)`` dispatches K optimizer steps as ONE ``lax.scan`` program
behind a device-feed stage, and must be indistinguishable from the per-step fit
in everything but dispatch count: bitwise-identical final parameters, per-step
losses, sentinel ``bad_steps`` accounting, exact ``on_anomaly`` step indices
(including a NaN landing mid-chunk), health cadence under the interleave, and
recovery rollbacks — all on the 8-device virtual CPU mesh.
"""

import numpy as np
import pytest

import jax

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, RecoveryPolicy, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.obs import HealthConfig
from replay_tpu.utils.faults import NaNInjector, SignalAtStep

NUM_ITEMS = 12
SEQ_LEN = 8
BATCH = 8  # divisible by the 8-device data axis


def make_schema() -> TensorSchema:
    # the numerical feature is the NaN-injection surface (ids can't carry NaN)
    return TensorSchema(
        [
            TensorFeatureInfo(
                "item_id",
                FeatureType.CATEGORICAL,
                is_seq=True,
                feature_hint=FeatureHint.ITEM_ID,
                cardinality=NUM_ITEMS,
                embedding_dim=16,
            ),
            TensorFeatureInfo(
                "num_feature", FeatureType.NUMERICAL, is_seq=True, tensor_dim=1,
                embedding_dim=16,
            ),
        ]
    )


def make_batch(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN + 1)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    return {
        "feature_tensors": {
            "item_id": items[:, :-1],
            "num_feature": rng.normal(size=(BATCH, SEQ_LEN)).astype(np.float32),
        },
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer(own_programs: bool = False, **kwargs) -> Trainer:
    """A trainer of the module's one tiny model. The plain ones run the same two
    programs and share them (traced and lowered once a module); ``own_programs``
    is for a trainer whose compile counts are asserted or whose programs the test
    rebuilds (recovery's LR backoff), and a configured one (``health=``) differs."""
    model = SasRec(
        schema=make_schema(), embedding_dim=16, num_blocks=1, num_heads=1,
        max_sequence_length=SEQ_LEN,
    )
    trainer = Trainer(
        model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2),
        mesh=make_mesh(), **kwargs,
    )
    if own_programs or kwargs:
        return PROGRAMS.share_init(trainer)  # the same model: the same fresh parameters
    return PROGRAMS.adopt(trainer)


class EventSink:
    def __init__(self):
        self.events = []

    def log_event(self, event):
        self.events.append(event)

    def named(self, name):
        return [e for e in self.events if e.event == name]


def assert_params_bitwise_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


def step_records(sink):
    """(step, loss) pairs from on_train_step events, NaN-tolerant compare."""
    out = []
    for event in sink.named("on_train_step"):
        loss = event.payload["loss"]
        out.append((event.step, None if not np.isfinite(loss) else float(loss)))
    return out


# --------------------------------------------------------------------------- #
# bitwise parity with the per-step fit
# --------------------------------------------------------------------------- #
@pytest.mark.jax
@pytest.mark.smoke
def test_chunked_fit_bitwise_parity_including_tail():
    """7 batches x 2 epochs with K=3: two scans + a per-step tail per epoch
    produce the exact per-step results — final params, per-step losses, epoch
    averages — through ONE compiled scan program + ONE per-step program."""
    batches = [make_batch(i) for i in range(7)]

    per_step = make_trainer()
    sink_a = EventSink()
    state_a = per_step.fit(batches, epochs=2, loggers=sink_a, log_every=0)

    chunked = make_trainer(own_programs=True)  # its compile counts are asserted
    sink_b = EventSink()
    state_b = chunked.fit(batches, epochs=2, loggers=sink_b, log_every=0, scan_chunk=3)

    assert_params_bitwise_equal(state_a.params, state_b.params)
    assert int(state_a.step) == int(state_b.step) == 14
    assert int(state_a.bad_steps) == int(state_b.bad_steps) == 0
    assert np.array_equal(np.asarray(state_a.rng), np.asarray(state_b.rng))
    assert per_step.history == chunked.history
    assert step_records(sink_a) == step_records(sink_b)
    # exactly one extra compiled variant: the K=3 scan next to the per-step
    # program that handles the tail — no chunk-length zoo
    compile_report = chunked.compile_tracker.report()
    assert compile_report["train_scan"]["traces"] == 1
    assert compile_report["train_step"]["traces"] == 1


@pytest.mark.jax
def test_device_feed_off_matches_on():
    """device_feed=False places chunks synchronously on the fit thread —
    slower, but the math and accounting must be identical."""
    batches = [make_batch(i) for i in range(6)]
    fed = make_trainer()
    state_a = fed.fit(batches, epochs=1, log_every=0, scan_chunk=2, device_feed=True)
    unfed = make_trainer()
    state_b = unfed.fit(batches, epochs=1, log_every=0, scan_chunk=2, device_feed=False)
    assert_params_bitwise_equal(state_a.params, state_b.params)
    assert fed.history == unfed.history


@pytest.mark.jax
@pytest.mark.smoke
def test_anomaly_indices_exact_with_nan_mid_chunk():
    """A NaN batch landing MID-chunk (position 4 → step 5, inside the K=3
    chunk covering steps 4-6) reports the exact per-step anomaly index,
    per-step bad_steps totals and losses — identical to the per-step fit."""

    def run(scan_chunk):
        injector = NaNInjector(at_steps=(4,))
        trainer = make_trainer()
        sink = EventSink()
        state = trainer.fit(
            lambda epoch: injector.wrap([make_batch(epoch * 10 + i) for i in range(7)]),
            epochs=2,
            loggers=sink,
            scan_chunk=scan_chunk,
            log_every=0,
        )
        return trainer, state, sink

    per_step, state_a, sink_a = run(None)
    chunked, state_b, sink_b = run(3)

    assert_params_bitwise_equal(state_a.params, state_b.params)
    assert int(state_a.bad_steps) == int(state_b.bad_steps) == 1
    anomalies_a = [(e.step, e.payload["bad_steps_total"]) for e in sink_a.named("on_anomaly")]
    anomalies_b = [(e.step, e.payload["bad_steps_total"]) for e in sink_b.named("on_anomaly")]
    assert anomalies_a == anomalies_b == [(5, 1)]
    assert step_records(sink_a) == step_records(sink_b)
    assert per_step.history == chunked.history


# --------------------------------------------------------------------------- #
# recovery rollback
# --------------------------------------------------------------------------- #
@pytest.mark.jax
def test_recovery_trigger_at_chunk_boundary_bitwise_parity():
    """The consecutive-bad trigger landing exactly at a chunk END (steps 5 and
    6 bad, K=3 chunk covers 4-6) rolls back at the same point as the per-step
    fit — bitwise-identical continuation."""

    def run(scan_chunk):
        injector = NaNInjector(at_steps=(4, 5))
        trainer = make_trainer(own_programs=True)  # the LR backoff rebuilds them
        sink = EventSink()
        state = trainer.fit(
            lambda epoch: injector.wrap([make_batch(i) for i in range(9)]),
            epochs=1,
            loggers=sink,
            scan_chunk=scan_chunk,
            log_every=0,
            recovery=RecoveryPolicy(max_consecutive_bad=2, max_restarts=2, lr_backoff=0.5),
        )
        return trainer, state, sink

    per_step, state_a, sink_a = run(None)
    chunked, state_b, sink_b = run(3)
    assert len(sink_a.named("on_recovery")) == len(sink_b.named("on_recovery")) == 1
    assert_params_bitwise_equal(state_a.params, state_b.params)
    assert per_step._lr_scale == chunked._lr_scale == pytest.approx(0.5)
    assert step_records(sink_a) == step_records(sink_b)


@pytest.mark.jax
def test_recovery_mid_chunk_discards_rest_of_chunk():
    """A trigger firing MID-chunk rolls back at chunk granularity: the
    remaining (already-executed, pre-rollback) steps of the chunk are consumed
    but not accounted, and the run continues finite on the restored state."""
    injector = NaNInjector(at_steps=(3, 4))  # steps 4, 5 — mid-chunk of 4-6
    trainer = make_trainer(own_programs=True)  # the LR backoff rebuilds them
    sink = EventSink()
    state = trainer.fit(
        lambda epoch: injector.wrap([make_batch(i) for i in range(7)]),
        epochs=1,
        loggers=sink,
        scan_chunk=3,
        log_every=0,
        recovery=RecoveryPolicy(max_consecutive_bad=2, max_restarts=2, lr_backoff=0.5),
    )
    recoveries = sink.named("on_recovery")
    assert len(recoveries) == 1
    assert recoveries[0].payload["reason"] == "consecutive_bad_steps"
    # rollback restored the initial snapshot (no checkpoints): step 6's update
    # belonged to the discarded trajectory, only the step-7 tail ran after —
    # and its event carries the restored trajectory's step id
    assert int(state.step) == 1
    assert int(state.bad_steps) == 0  # the rollback restored the clean snapshot
    assert np.isfinite(trainer.history[-1]["train_loss"])
    # step 6 (rest of the rolled-back chunk) emitted no on_train_step event
    emitted_steps = [e.step for e in sink.named("on_train_step")]
    assert 6 not in emitted_steps


# --------------------------------------------------------------------------- #
# health interleave
# --------------------------------------------------------------------------- #
@pytest.mark.jax
def test_health_cadence_interleaves_single_steps():
    """HealthConfig + scan_chunk: every cadence-th step runs the health
    program (no silent health loss), the rest still run through ONE scan
    program, and the math matches a plain per-step fit bitwise."""
    batches = [make_batch(i) for i in range(8)]
    # cadence ≡ 1 (mod K): chunks (1,2), (3,4), health single 5, (6,7), tail 8
    chunked = make_trainer(health=HealthConfig(cadence=5))
    sink = EventSink()
    state_a = chunked.fit(batches, epochs=1, loggers=sink, log_every=0, scan_chunk=2)

    health_steps = [
        e.step for e in sink.named("on_train_step") if "health" in e.payload
    ]
    assert health_steps == [5]
    assert chunked.last_health is not None
    compile_report = chunked.compile_tracker.report()
    assert compile_report["train_scan"]["traces"] == 1
    assert compile_report["train_step"]["traces"] == 1  # the health variant

    plain = make_trainer()
    state_b = plain.fit(batches, epochs=1, log_every=0)
    assert_params_bitwise_equal(state_a.params, state_b.params)


# --------------------------------------------------------------------------- #
# chunk-boundary checkpointing + preemption
# --------------------------------------------------------------------------- #
@pytest.mark.jax
def test_checkpoint_every_saves_at_chunk_boundaries(tmp_path):
    """A checkpoint_every boundary crossed INSIDE a chunk saves once at the
    chunk end with the chunk-end stream position — resume-consistent."""
    from replay_tpu.utils.checkpoint import CheckpointManager

    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=10)
    trainer = make_trainer()
    trainer.fit(
        [make_batch(i) for i in range(7)],
        epochs=1,
        checkpoint_manager=manager,
        checkpoint_every=2,  # boundaries at 2, 4, 6 — all inside K=3 chunks
        scan_chunk=3,
        log_every=0,
    )
    mid_epoch = sorted(
        step for step in manager.valid_steps() if manager.metadata(step).get("mid_epoch")
    )
    # chunk ends at 3 and 6 covered boundaries 2 and (4, 6); the position
    # stamped is the chunk end, where the saved state actually exists
    assert mid_epoch == [3, 6]
    for step in mid_epoch:
        assert manager.metadata(step)["step_in_epoch"] == step


@pytest.mark.jax
def test_preemption_mid_chunked_fit_resumes_bit_for_bit(tmp_path):
    """A SIGTERM during a chunked fit checkpoints at a chunk boundary and
    fit(resume=True, scan_chunk=...) reproduces the uninterrupted run."""
    from replay_tpu.utils.checkpoint import CheckpointManager

    batches = [make_batch(i) for i in range(9)]

    uninterrupted = make_trainer()
    final_a = uninterrupted.fit(batches, epochs=1, log_every=0, scan_chunk=3)

    preempted = make_trainer()
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=10)
    harness = SignalAtStep(at_step=2)
    mid = preempted.fit(
        lambda epoch: harness.wrap(iter(batches)),
        epochs=1,
        checkpoint_manager=manager,
        scan_chunk=3,
        log_every=0,
    )
    assert int(mid.step) < 9  # actually exited early, at a chunk boundary
    resumed_trainer = make_trainer()
    final_b = resumed_trainer.fit(
        batches,
        epochs=1,
        checkpoint_manager=manager,
        resume=True,
        scan_chunk=3,
        log_every=0,
    )
    assert int(final_b.step) == int(final_a.step) == 9
    assert_params_bitwise_equal(final_a.params, final_b.params)


# --------------------------------------------------------------------------- #
# guards
# --------------------------------------------------------------------------- #
@pytest.mark.jax
def test_bucketed_batcher_rejected_at_fit_start():
    import pandas as pd

    from replay_tpu.data.nn import SequenceBatcher, SequentialDataset

    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=NUM_ITEMS,
        )
    )
    frame = pd.DataFrame(
        {"query_id": np.arange(6), "item_id": [np.arange(1 + i) for i in range(6)]}
    )
    dataset = SequentialDataset(schema, "query_id", "item_id", frame)
    bucketed = SequenceBatcher(
        dataset, batch_size=2, max_sequence_length=6, bucket_boundaries=(3,)
    )
    assert not bucketed.scan_compatible
    trainer = make_trainer()
    with pytest.raises(ValueError, match="bucket_boundaries"):
        trainer.fit(bucketed, epochs=1, scan_chunk=2)
    # a factory callable hides the batcher from the fit-start check; the
    # epoch-start check rejects what it returns before any step runs
    with pytest.raises(ValueError, match="bucket_boundaries"):
        trainer.fit(lambda: bucketed, epochs=1, scan_chunk=2)


@pytest.mark.jax
def test_scan_chunk_must_be_positive():
    trainer = make_trainer()
    with pytest.raises(ValueError, match="scan_chunk"):
        trainer.fit([make_batch(0)], epochs=1, scan_chunk=0)
