"""Fault-tolerant training: every recovery path exercised deterministically.

The resilience layer (docs/robustness.md) under injected faults from
``replay_tpu.utils.faults`` on the 8-device virtual CPU mesh:

* the in-jit non-finite sentinel skips NaN batches bit-for-bit and reports the
  exact injected step indices through ``on_anomaly`` events;
* ``RecoveryPolicy`` rolls back to the last checkpoint with LR backoff, bounded
  by its max-restarts budget;
* a real SIGTERM mid-epoch checkpoints at the step boundary, and
  ``fit(resume=True)`` reproduces the uninterrupted run's final loss and
  parameters bit-for-bit (the acceptance gate for this layer).

The smoke tests double as the CI artifact source: their ``events.jsonl``
(anomaly + recovery events) lands in ``REPLAY_TPU_RUN_DIR`` and ships from the
``jax and smoke`` workflow job.
"""

import json
import os

import numpy as np
import pytest

import jax

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, RecoveryPolicy, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.obs import JsonlLogger
from replay_tpu.utils.checkpoint import CheckpointManager
from replay_tpu.utils.faults import NaNInjector, SignalAtStep, inject_nan, truncate_file

NUM_ITEMS = 12
SEQ_LEN = 8
BATCH = 8  # divisible by the 8-device data axis


def _run_dir(tmp_path, name):
    """CI exports REPLAY_TPU_RUN_DIR so the smoke run's recovery telemetry
    ships as a workflow artifact; locally the run log lands in tmp_path."""
    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    return os.path.join(base, name) if base else str(tmp_path / name)


def make_schema() -> TensorSchema:
    # the numerical feature is the NaN-injection surface: integer ids cannot
    # carry a NaN, a poisoned float feature drives loss AND grads non-finite
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


def make_trainer(own_programs: bool = False) -> Trainer:
    """A trainer of the module's one tiny model. The plain ones share their two
    programs (traced and lowered once a module); ``own_programs`` is for a
    trainer whose fit rebuilds them (a RecoveryPolicy's LR backoff)."""
    model = SasRec(
        schema=make_schema(), embedding_dim=16, num_blocks=1, num_heads=1,
        max_sequence_length=SEQ_LEN,
    )
    trainer = Trainer(
        model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2),
        mesh=make_mesh(),
    )
    if own_programs:
        return PROGRAMS.share_init(trainer)  # the same model: the same fresh parameters
    return PROGRAMS.adopt(trainer)


class EventSink:
    def __init__(self):
        self.events = []

    def log_event(self, event):
        self.events.append(event)

    def named(self, name):
        return [e for e in self.events if e.event == name]


def assert_trees_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)), a, b
    )


# --------------------------------------------------------------------------- #
# non-finite sentinel
# --------------------------------------------------------------------------- #
@pytest.mark.jax
@pytest.mark.smoke
def test_sentinel_keeps_state_bit_for_bit_on_nan_batch():
    """A NaN batch must not move a single parameter or optimizer bit; step and
    rng still advance so the batch-stream alignment survives."""
    trainer = make_trainer()
    state = trainer.init_state(make_batch(0))
    state, _ = trainer.train_step(state, make_batch(0))
    params_before = jax.tree.map(np.asarray, state.params)
    opt_before = jax.tree.map(np.asarray, state.opt_state)
    rng_before = np.asarray(state.rng)

    state, loss = trainer.train_step(state, inject_nan(make_batch(1)))
    assert not np.isfinite(float(loss))
    assert not bool(trainer.last_step_metrics["good"])
    assert not np.isfinite(float(trainer.last_step_metrics["grad_norm"]))
    assert_trees_equal(params_before, state.params)
    assert_trees_equal(opt_before, state.opt_state)
    assert int(state.step) == 2  # the skipped step still consumed a step id
    assert int(state.bad_steps) == 1
    assert not np.array_equal(rng_before, np.asarray(state.rng))  # rng advanced

    # and training continues finite right after the poisoned batch
    state, loss = trainer.train_step(state, make_batch(2))
    assert np.isfinite(float(loss))
    assert int(state.bad_steps) == 1


@pytest.mark.jax
@pytest.mark.smoke
def test_nan_injection_reports_exact_steps_and_finishes_finite(tmp_path):
    """Acceptance: a seeded run injected with NaN batches at fixed steps ends
    with finite loss and on_anomaly events at exactly the injected indices."""
    injector = NaNInjector(at_steps=(2, 5))  # 0-based global batch positions
    trainer = make_trainer()
    run_dir = _run_dir(tmp_path, "fault_smoke")
    # mode="w": REPLAY_TPU_RUN_DIR is a fixed path in CI — a re-run must not
    # append a second event stream and break the counts below
    with JsonlLogger(run_dir, mode="w") as sink:
        state = trainer.fit(
            lambda epoch: injector.wrap([make_batch(epoch * 10 + i) for i in range(4)]),
            epochs=2,
            loggers=sink,
        )

    assert injector.injected_at == [2, 5]
    assert int(state.bad_steps) == 2
    lines = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    anomalies = [line for line in lines if line["event"] == "on_anomaly"]
    # state.step is 1-based: global batch positions 2 and 5 are steps 3 and 6
    assert [a["step"] for a in anomalies] == [3, 6]
    assert all(a["loss"] is None for a in anomalies)  # non-finite → JSON null
    steps = [line for line in lines if line["event"] == "on_train_step"]
    assert len(steps) == 8
    bad = {s["step"]: s for s in steps if s["loss"] is None}
    assert sorted(bad) == [3, 6]  # only the injected steps lost their loss
    # the epoch records average sentinel-approved steps only: finite throughout
    assert all(np.isfinite(r["train_loss"]) for r in trainer.history)
    fit_end = lines[-1]
    assert fit_end["event"] == "on_fit_end" and fit_end["bad_steps"] == 2


@pytest.mark.jax
def test_detect_anomalies_defaults_off_without_loggers_or_recovery():
    """log_every-only runs stay per-step-sync-free: no anomaly events, but the
    sentinel still protects the state and counts the skipped step."""
    injector = NaNInjector(at_steps=(1,))
    trainer = make_trainer()
    state = trainer.fit(
        lambda epoch: injector.wrap([make_batch(i) for i in range(3)]), epochs=1,
    )
    assert int(state.bad_steps) == 1
    assert np.isfinite(trainer.history[-1]["train_loss"])


# --------------------------------------------------------------------------- #
# RecoveryPolicy
# --------------------------------------------------------------------------- #
@pytest.mark.jax
@pytest.mark.smoke
def test_recovery_rolls_back_to_checkpoint_with_lr_backoff(tmp_path):
    injector = NaNInjector(at_steps=(3, 4, 5))  # >= max_consecutive_bad in a row
    trainer = make_trainer(own_programs=True)
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=10)
    sink = EventSink()
    state = trainer.fit(
        lambda epoch: injector.wrap([make_batch(i) for i in range(8)]),
        epochs=1,
        checkpoint_manager=manager,
        checkpoint_every=2,
        recovery=RecoveryPolicy(max_consecutive_bad=3, max_restarts=2, lr_backoff=0.5),
        loggers=sink,
    )
    assert len(sink.named("on_anomaly")) == 3
    recoveries = sink.named("on_recovery")
    assert len(recoveries) == 1
    payload = recoveries[0].payload
    # checkpoint_every=2 saved steps 2 and 4 before the third bad step hit;
    # sentinel-protected, so even the step-4 checkpoint holds good params
    assert payload["reason"] == "consecutive_bad_steps"
    assert payload["restored_step"] == 4
    assert payload["lr_scale"] == pytest.approx(0.5)
    assert trainer._lr_scale == pytest.approx(0.5)
    assert np.isfinite(trainer.history[-1]["train_loss"])
    assert int(state.step) > 4  # training continued past the rollback


@pytest.mark.jax
def test_recovery_budget_exhausted_raises():
    """Restarts are bounded: a run that keeps producing bad steps raises
    instead of burning the remaining budget (no checkpoint manager → rollback
    targets the initial-state snapshot)."""
    injector = NaNInjector(at_steps=range(2, 10))
    trainer = make_trainer(own_programs=True)
    with pytest.raises(RuntimeError, match="budget exhausted"):
        trainer.fit(
            lambda epoch: injector.wrap([make_batch(i) for i in range(12)]),
            epochs=1,
            recovery=RecoveryPolicy(max_consecutive_bad=2, max_restarts=1),
        )


@pytest.mark.jax
def test_recovery_metric_blowup_triggers_rollback(tmp_path):
    """An epoch whose monitored loss goes non-finite (every step sentinel-
    skipped → nothing measured) rolls back at the epoch boundary instead of
    checkpointing the diverged epoch — max_consecutive_bad is set high enough
    that the per-step trigger stays out of the way."""
    injector = NaNInjector(at_steps=(3, 4, 5))  # all of epoch 1's batches

    def train_batches(epoch: int):
        return injector.wrap([make_batch(epoch * 10 + i) for i in range(3)])

    trainer = make_trainer(own_programs=True)
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=10)
    sink = EventSink()
    trainer.fit(
        train_batches,
        epochs=3,
        checkpoint_manager=manager,
        monitor="train_loss",
        mode="min",
        recovery=RecoveryPolicy(max_consecutive_bad=10, max_restarts=2, blowup_factor=1.5),
        loggers=sink,
    )
    recoveries = sink.named("on_recovery")
    assert len(recoveries) == 1
    assert recoveries[0].payload["reason"] == "metric_blowup"
    assert recoveries[0].epoch == 1
    # the poisoned epoch's record is in history (NaN), but the diverged epoch
    # never became a checkpoint: the rollback target was epoch 0's save
    assert recoveries[0].payload["restored_step"] == 3
    assert not np.isfinite(trainer.history[1]["train_loss"])
    assert np.isfinite(trainer.history[-1]["train_loss"])


@pytest.mark.jax
def test_recovery_triggers_even_with_detect_anomalies_off():
    """detect_anomalies=False silences the on_anomaly events, never the
    rollback trigger: the policy still counts bad steps and still bounds the
    restart budget."""
    injector = NaNInjector(at_steps=range(2, 10))
    trainer = make_trainer(own_programs=True)
    sink = EventSink()
    with pytest.raises(RuntimeError, match="budget exhausted"):
        trainer.fit(
            lambda epoch: injector.wrap([make_batch(i) for i in range(12)]),
            epochs=1,
            recovery=RecoveryPolicy(max_consecutive_bad=2, max_restarts=1),
            detect_anomalies=False,
            loggers=sink,
        )
    assert sink.named("on_anomaly") == []  # silenced
    assert len(sink.named("on_recovery")) == 2  # trigger + exhausted


@pytest.mark.jax
def test_recovery_policy_validates():
    with pytest.raises(ValueError, match="max_consecutive_bad"):
        RecoveryPolicy(max_consecutive_bad=0)
    with pytest.raises(ValueError, match="lr_backoff"):
        RecoveryPolicy(lr_backoff=0.0)
    with pytest.raises(ValueError, match="blowup_factor"):
        RecoveryPolicy(blowup_factor=1.0)


# --------------------------------------------------------------------------- #
# preemption
# --------------------------------------------------------------------------- #
@pytest.mark.jax
@pytest.mark.smoke
def test_sigterm_mid_epoch_then_resume_is_bit_for_bit(tmp_path):
    """Acceptance: SIGTERM mid-epoch → position-stamped checkpoint + clean
    exit; fit(resume=True) reproduces the uninterrupted run's final loss and
    parameters bit-for-bit."""

    def stream(epoch: int):
        return [make_batch(epoch * 100 + i) for i in range(5)]

    trainer_a = make_trainer()
    manager_a = CheckpointManager(str(tmp_path / "a"), max_to_keep=100)
    state_a = trainer_a.fit(stream, epochs=2, checkpoint_manager=manager_a)

    # the signal fires through the real OS machinery while batch 2 is fetched
    trainer_b = make_trainer()
    manager_b = CheckpointManager(str(tmp_path / "b"), max_to_keep=100)
    sig = SignalAtStep(2)
    sink = EventSink()
    state_mid = trainer_b.fit(
        lambda epoch: sig.wrap(stream(epoch)), epochs=2,
        checkpoint_manager=manager_b, loggers=sink,
    )
    assert sig.raised
    assert int(state_mid.step) < int(state_a.step)
    preempt = sink.named("on_preemption")
    assert len(preempt) == 1 and preempt[0].payload["signal"] == "SIGTERM"
    assert sink.events[-1].event == "on_fit_end" and sink.events[-1].payload["preempted"]
    meta = manager_b.metadata(manager_b.latest_step())
    assert meta["preempted"] and meta["mid_epoch"] and meta["epoch"] == 0

    # a fresh process resumes from the preemption checkpoint
    trainer_c = make_trainer()
    state_c = trainer_c.fit(stream, epochs=2, checkpoint_manager=manager_b, resume=True)
    assert int(state_c.step) == int(state_a.step)
    assert_trees_equal(state_a.params, state_c.params)
    assert_trees_equal(state_a.opt_state, state_c.opt_state)
    np.testing.assert_array_equal(np.asarray(state_a.rng), np.asarray(state_c.rng))
    # the final (fully-measured) epoch's loss is bit-identical
    assert trainer_a.history[-1]["train_loss"] == trainer_c.history[-1]["train_loss"]


@pytest.mark.jax
def test_preemption_saves_trace_and_flight_ring(tmp_path, monkeypatch):
    """A preempted traced fit must not lose its span tree: ``trace.json`` is
    flushed eagerly at the ``on_preemption`` emission — BEFORE the shutdown-
    window checkpoint save, so even a save that dies cannot take the trace
    with it — and the flight ring (``REPLAY_TPU_FLIGHT_PATH``) holds the
    preemption as its final records."""
    from replay_tpu.obs.blackbox import read_flight
    from replay_tpu.obs.report import load_trace_events

    trace_path = str(tmp_path / "trace.json")
    ring_path = str(tmp_path / "flight.ring")
    monkeypatch.setenv("REPLAY_TPU_FLIGHT_PATH", ring_path)

    sig = SignalAtStep(2)
    trainer = make_trainer()
    manager = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=100)
    state = trainer.fit(
        lambda epoch: sig.wrap([make_batch(epoch * 100 + i) for i in range(5)]),
        epochs=2, checkpoint_manager=manager,
        tracer=True, trace_path=trace_path,
    )
    assert sig.raised and int(state.step) < 10  # preempted well short of 2 epochs

    # the trace survived the preemption with real spans in it
    events = load_trace_events(trace_path)
    assert any(event["name"] == "train_step" for event in events)

    # the ring's story ends with the preemption sequence, readable post-exit
    log = read_flight(ring_path)
    assert not log.torn_tail
    names = [r["event"] for r in log.records]
    assert "on_preemption" in names
    preempt = next(r for r in log.records if r["event"] == "on_preemption")
    assert preempt["signal"] == "SIGTERM"
    assert names[-1] == "on_fit_end"
    assert log.records[-1]["preempted"] is True


@pytest.mark.jax
def test_lr_backoff_survives_preemption_and_resume(tmp_path):
    """A run that rolled back (LR scale 0.5) and is then preempted must resume
    at the backed-off rate, not rerun the divergence at full LR."""
    injector = NaNInjector(at_steps=(2, 3))  # trigger one rollback...
    sig = SignalAtStep(6)  # ...then preempt later in the same epoch

    def stream(epoch: int):
        return sig.wrap(injector.wrap([make_batch(epoch * 100 + i) for i in range(9)]))

    trainer_a = make_trainer(own_programs=True)
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=100)
    policy = RecoveryPolicy(max_consecutive_bad=2, max_restarts=3, lr_backoff=0.5)
    trainer_a.fit(
        stream, epochs=1, checkpoint_manager=manager, checkpoint_every=2,
        recovery=policy,
    )
    assert trainer_a._lr_scale == pytest.approx(0.5)
    assert manager.metadata(manager.latest_step())["lr_scale"] == pytest.approx(0.5)

    trainer_b = make_trainer(own_programs=True)
    assert trainer_b._lr_scale == 1.0
    trainer_b.fit(
        lambda epoch: [make_batch(epoch * 100 + i) for i in range(9)],
        epochs=1, checkpoint_manager=manager, recovery=policy, resume=True,
    )
    assert trainer_b._lr_scale == pytest.approx(0.5)  # restored from metadata


@pytest.mark.jax
def test_second_signal_restores_previous_handler():
    """The handler context restores whatever was installed before fit."""
    import signal as _signal

    from replay_tpu.nn import PreemptionHandler

    sentinel = []
    previous = _signal.signal(_signal.SIGTERM, lambda *a: sentinel.append("previous"))
    try:
        with PreemptionHandler() as handler:
            _signal.raise_signal(_signal.SIGTERM)
            assert handler.requested and handler.signal_name == "SIGTERM"
            _signal.raise_signal(_signal.SIGTERM)  # second: previous handler
            assert sentinel == ["previous"]
        # context exit restored the pre-fit handler
        _signal.raise_signal(_signal.SIGTERM)
        assert sentinel == ["previous", "previous"]
    finally:
        _signal.signal(_signal.SIGTERM, previous)


# --------------------------------------------------------------------------- #
# corrupt / truncated checkpoints
# --------------------------------------------------------------------------- #
@pytest.mark.jax
def test_truncated_latest_checkpoint_skipped_and_reported(tmp_path):
    def stream(epoch: int):
        return [make_batch(epoch * 10 + i) for i in range(3)]

    trainer_a = make_trainer()
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=100)
    state_a = trainer_a.fit(stream, epochs=2, checkpoint_manager=manager)
    latest = manager.latest_step()
    truncate_file(str(tmp_path / "run" / f"step_{latest}.npz"), keep_fraction=0.4)

    # latest_step skips the torn file and reports it instead of raising
    assert manager.latest_step() == 3  # the epoch-0 checkpoint
    assert manager.skipped_steps == [latest]

    # resume re-trains epoch 1 from the surviving checkpoint: same final state
    trainer_b = make_trainer()
    state_b = trainer_b.fit(stream, epochs=2, checkpoint_manager=manager, resume=True)
    assert int(state_b.step) == int(state_a.step)
    assert_trees_equal(state_a.params, state_b.params)


@pytest.mark.jax
def test_restore_of_corrupt_step_names_the_step(tmp_path):
    """Satellite: an explicit restore of a torn/corrupt step raises a clear
    error naming it, not a bare deserialization traceback."""
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=10)
    tree = {"w": np.arange(64, dtype=np.float32)}
    manager.save(3, tree)
    truncate_file(str(tmp_path / "run" / "step_3.npz"), keep_fraction=0.3)
    with pytest.raises(ValueError, match="step_3"):
        manager.restore({"w": np.zeros(64, np.float32)}, step=3)

    manager.save(5, tree)
    (tmp_path / "run" / "step_5.json").write_text("{not json")
    with pytest.raises(ValueError, match="step_5"):
        manager.restore({"w": np.zeros(64, np.float32)}, step=5)

    manager.save(7, tree)
    with pytest.raises(ValueError, match="step_7.*num_leaves|num_leaves.*step_7"):
        manager.restore({"w": np.zeros(64, np.float32), "b": np.zeros(2)}, step=7)


@pytest.mark.jax
def test_interrupted_save_invisible_to_resume(tmp_path):
    """A payload without its sidecar (killed between the two writes) and a
    sidecar without its payload are both treated as aborted saves."""
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=10)
    manager.save(5, {"w": np.ones(4, np.float32)})
    # payload landed, commit marker (sidecar) did not:
    (tmp_path / "run" / "step_7.npz").write_bytes(b"torn half-write")
    # sidecar landed without payload (or payload deleted under us):
    (tmp_path / "run" / "step_9.json").write_text(json.dumps({"step": 9, "backend": "npz"}))

    assert manager.all_steps() == [5, 9]  # sidecars drive enumeration
    assert manager.latest_step() == 5
    assert manager.skipped_steps == [9]
    restored = manager.restore({"w": np.zeros(4, np.float32)})
    np.testing.assert_array_equal(restored["w"], np.ones(4))


@pytest.mark.jax
def test_atomic_save_leaves_no_temp_files(tmp_path):
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=10)
    for step in (1, 2):
        manager.save(step, {"w": np.ones(8, np.float32)})
    leftovers = [p.name for p in (tmp_path / "run").glob("*.tmp")]
    assert leftovers == []
