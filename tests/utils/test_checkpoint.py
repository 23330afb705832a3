"""Checkpointing: pytree round-trip, retention, kill-and-resume loss-curve parity."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.utils.checkpoint import CheckpointManager, load_metadata, restore_pytree, save_pytree

NUM_ITEMS = 10
SEQ_LEN = 5
BATCH = 8


def make_batch(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN + 1)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    return {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer(learning_rate: float = 1e-2) -> Trainer:
    """The module's one tiny model; trainers of one learning rate share their
    two programs (traced and lowered once a module)."""
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
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = Trainer(model=model, loss=CE(),
                      optimizer=OptimizerFactory(learning_rate=learning_rate),
                      mesh=make_mesh(), seed=0)
    return PROGRAMS.adopt(trainer, key=learning_rate)


@pytest.mark.jax
def test_pytree_roundtrip_and_validation(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": [jnp.zeros(4), jnp.ones(())]}
    save_pytree(str(tmp_path / "ckpt"), tree, {"note": "x"})
    restored = restore_pytree(str(tmp_path / "ckpt"), jax.tree.map(np.zeros_like, tree))
    jax.tree.map(np.testing.assert_array_equal, jax.tree.map(np.asarray, tree), restored)
    assert load_metadata(str(tmp_path / "ckpt"))["note"] == "x"
    with pytest.raises(ValueError, match="leaves"):
        restore_pytree(str(tmp_path / "ckpt"), {"a": np.zeros((2, 3))})
    with pytest.raises(ValueError, match="shape"):
        restore_pytree(
            str(tmp_path / "ckpt"), {"a": np.zeros((9, 9)), "b": [np.zeros(4), np.ones(())]}
        )


@pytest.mark.jax
def test_kill_and_resume_reproduces_loss_curve(tmp_path):
    """3 steps + save + restore + 3 steps == 6 uninterrupted steps, exactly."""
    batches = [make_batch(i) for i in range(6)]

    trainer_a = make_trainer()
    state = trainer_a.init_state(batches[0])
    losses_a = []
    for batch in batches:
        state, loss_value = trainer_a.train_step(state, batch)
        losses_a.append(float(loss_value))

    trainer_b = make_trainer()
    state_b = trainer_b.init_state(batches[0])
    losses_b = []
    for batch in batches[:3]:
        state_b, loss_value = trainer_b.train_step(state_b, batch)
        losses_b.append(float(loss_value))
    trainer_b.save_checkpoint(str(tmp_path / "mid"), state_b)

    trainer_c = make_trainer()  # fresh process equivalent
    state_c = trainer_c.restore_checkpoint(str(tmp_path / "mid"), batches[0])
    assert int(state_c.step) == 3
    for batch in batches[3:]:
        state_c, loss_value = trainer_c.train_step(state_c, batch)
        losses_b.append(float(loss_value))

    np.testing.assert_allclose(np.array(losses_a), np.array(losses_b), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6),
        state.params,
        state_c.params,
    )


@pytest.mark.jax
def test_manager_retention_and_history(tmp_path):
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=2)
    assert manager.latest_step() is None
    tree = {"w": jnp.ones(3)}
    for step in (1, 2, 3):
        manager.save(step, tree, history=[{"epoch": step, "train_loss": 1.0 / step}])
    assert manager.all_steps() == [2, 3]
    assert manager.latest_step() == 3
    restored = manager.restore({"w": np.zeros(3)})
    np.testing.assert_array_equal(restored["w"], np.ones(3))
    assert manager.history()[-1]["epoch"] == 3
    with pytest.raises(FileNotFoundError):
        CheckpointManager(str(tmp_path / "empty")).restore({"w": np.zeros(3)})


@pytest.mark.jax
def test_sigkill_mid_save_never_corrupts_the_manager(tmp_path):
    """Hard-kill atomicity: a writer SIGKILLed inside ``save_pytree`` — while
    payload bytes are in flight, or after the payload but before the JSON
    commit marker — leaves the directory in a state where ``valid_steps``
    skips the partial step and the PRIOR step restores bit-identically."""
    import subprocess
    import sys
    from pathlib import Path

    worker = Path(__file__).with_name("ckpt_kill_worker.py")
    ckpt_dir = tmp_path / "ckpt"

    def run(phase):
        return subprocess.run(
            [sys.executable, str(worker), str(ckpt_dir), phase],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "PYTHONPATH": str(worker.parents[2])},
        )

    assert run("baseline").returncode == 0, "baseline save failed"
    step1_npz = (ckpt_dir / "step_1.npz").read_bytes()
    step1_json = (ckpt_dir / "step_1.json").read_bytes()

    import signal as _signal

    for phase in ("mid_payload", "pre_sidecar"):
        proc = run(phase)
        assert proc.returncode == -_signal.SIGKILL, (phase, proc.stderr[-500:])
        manager = CheckpointManager(str(ckpt_dir), max_to_keep=10)
        assert manager.valid_steps() == [1], phase
        assert manager.latest_step() == 1, phase
        # the partial step never becomes a visible, torn checkpoint
        if phase == "mid_payload":
            assert (ckpt_dir / "step_2.npz.tmp").exists()
            assert not (ckpt_dir / "step_2.npz").exists()
        else:
            assert (ckpt_dir / "step_2.npz").exists()  # payload published...
            assert not (ckpt_dir / "step_2.json").exists()  # ...never committed
        # the prior step's files are byte-identical and restore exactly
        assert (ckpt_dir / "step_1.npz").read_bytes() == step1_npz, phase
        assert (ckpt_dir / "step_1.json").read_bytes() == step1_json, phase
        import importlib.util

        spec = importlib.util.spec_from_file_location("ckpt_kill_worker", worker)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        expected = module.make_tree(1)
        restored = manager.restore(
            {k: np.zeros_like(v) for k, v in expected.items()}, step=1
        )
        for key in expected:
            np.testing.assert_array_equal(restored[key], expected[key])
        # cleanup for the next phase: kill the stray step-2 leftovers
        for leftover in ckpt_dir.glob("step_2*"):
            leftover.unlink()


@pytest.mark.jax
def test_process_metadata_sidecar_roundtrip_and_rotation(tmp_path):
    """Per-process sidecars: written atomically by each rank, read back by
    the same rank, and rotated away with their step."""
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=1)
    tree = {"w": jnp.ones(3)}
    cursor = {"stream_cursor": {"epoch": 0, "slab": 2, "rows": 8, "batches": 5}}
    manager.save(1, tree, process_metadata=cursor)
    assert manager.process_metadata(1) == cursor
    assert manager.process_metadata(1, process_index=7) == {}  # another rank's
    assert manager.process_metadata(99) == {}  # absent step
    manager.save(2, tree, process_metadata={"stream_cursor": {"batches": 9}})
    assert manager.all_steps() == [2]  # step 1 rotated out...
    assert manager.process_metadata(1) == {}  # ...with its process sidecar
    assert manager.process_metadata(2)["stream_cursor"]["batches"] == 9


@pytest.mark.jax
def test_fit_saves_checkpoints(tmp_path):
    trainer = make_trainer()
    manager = CheckpointManager(str(tmp_path / "fit"), max_to_keep=5)
    batches = [make_batch(i) for i in range(3)]
    state = trainer.fit(lambda epoch: batches, epochs=2, checkpoint_manager=manager)
    assert manager.latest_step() == int(state.step)
    assert len(manager.history()) == 2

@pytest.mark.jax
def test_best_checkpoint_survives_rotation(tmp_path):
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=2)
    tree = {"w": jnp.ones(2)}
    manager.save(1, tree)
    manager.mark_best(1)
    for step in (2, 3, 4, 5):
        manager.save(step, {"w": jnp.ones(2) * step})
    assert 1 in manager.all_steps()  # the best survives max_to_keep=2
    assert manager.best_step() == 1
    best = manager.restore_best({"w": np.zeros(2)})
    np.testing.assert_array_equal(best["w"], np.ones(2))


@pytest.mark.jax
def test_orbax_backend_roundtrip_and_rotation(tmp_path):
    """The orbax storage backend round-trips TrainStates and rotates cleanly."""
    pytest.importorskip("orbax.checkpoint")
    trainer = make_trainer()
    state = trainer.init_state(make_batch(0))
    state, _ = trainer.train_step(state, make_batch(0))
    manager = CheckpointManager(str(tmp_path / "orbax_run"), max_to_keep=2, backend="orbax")
    for step in (1, 2, 3):
        manager.save(step, state)
    assert manager.all_steps() == [2, 3]  # rotation removed the orbax dir too
    assert not (tmp_path / "orbax_run" / "step_1.orbax").exists()
    template = trainer.init_state(make_batch(0))
    restored = manager.restore(template)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6),
        restored.params,
        state.params,
    )
    with pytest.raises(ValueError, match="backend"):
        from replay_tpu.utils.checkpoint import save_pytree
        save_pytree(str(tmp_path / "x"), {"a": jnp.ones(2)}, backend="zzz")


@pytest.mark.jax
def test_restore_rejects_dtype_mismatch(tmp_path):
    """A checkpoint saved from a different-precision config is a hard error,
    not a silent mixed-precision restore."""
    save_pytree(str(tmp_path / "f32"), {"w": jnp.ones((2, 2), jnp.float32)})
    with pytest.raises(ValueError, match="dtype"):
        restore_pytree(str(tmp_path / "f32"), {"w": np.zeros((2, 2), np.float16)})


@pytest.mark.jax
def test_orbax_abstract_target_carries_sharding(tmp_path):
    """Orbax restore targets built from live jax.Arrays keep their sharding, so
    restore does not fall back to (topology-unsafe) sharding-from-file."""
    pytest.importorskip("orbax.checkpoint")
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh()
    tree = {"w": jax.device_put(jnp.ones((4, 4)), NamedSharding(mesh, P()))}
    save_pytree(str(tmp_path / "s"), tree, backend="orbax")
    with np.errstate(all="ignore"):
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("error", UserWarning)  # sharding-from-file warns
            restored = restore_pytree(str(tmp_path / "s"), tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.ones((4, 4)))


@pytest.mark.jax
def test_trainer_save_checkpoint_backend_param(tmp_path):
    """Trainer.save_checkpoint honors an explicit backend choice."""
    pytest.importorskip("orbax.checkpoint")
    trainer = make_trainer()
    state = trainer.init_state(make_batch(0))
    trainer.save_checkpoint(str(tmp_path / "ck"), state, backend="orbax")
    assert (tmp_path / "ck.orbax").exists()
    restored = trainer.restore_checkpoint(str(tmp_path / "ck"), make_batch(0))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6),
        restored.params,
        state.params,
    )


@pytest.mark.jax
def test_mid_epoch_exact_resume(tmp_path):
    """A run killed mid-epoch and resumed reproduces the uninterrupted run's
    final parameters EXACTLY: the checkpoint records the data-iterator position
    (epoch + step within epoch) and fit fast-forwards the deterministic
    batch stream to it."""

    def train_batches(epoch: int):
        # deterministic per-epoch stream (the SequenceBatcher set_epoch contract)
        return [make_batch(epoch * 100 + i) for i in range(7)]

    # uninterrupted reference run: 2 epochs, mid-epoch checkpoints every 3 steps
    trainer_a = make_trainer()
    manager_a = CheckpointManager(str(tmp_path / "a"), max_to_keep=100)
    state_a = trainer_a.fit(
        train_batches, epochs=2, checkpoint_manager=manager_a, checkpoint_every=3,
    )

    # simulate the kill: keep only checkpoints up to mid-epoch-1-step-3
    # (epoch 1 = second epoch; 7 steps/epoch -> global step 10)
    manager_b = CheckpointManager(str(tmp_path / "b"), max_to_keep=100)
    import shutil

    for step in manager_a.all_steps():
        if step <= 10:
            for suffix in (".npz", ".json"):
                src = (tmp_path / "a" / f"step_{step}").with_suffix(suffix)
                if src.exists():
                    shutil.copy(src, tmp_path / "b" / src.name)
    assert manager_b.latest_step() == 10
    from replay_tpu.utils.checkpoint import load_metadata

    meta = load_metadata(str(tmp_path / "b" / "step_10"))
    assert meta["mid_epoch"] and meta["epoch"] == 1 and meta["step_in_epoch"] == 3

    # resume in a FRESH trainer: restores step 10, fast-forwards 3 batches of
    # epoch 1, finishes the run
    trainer_b = make_trainer()
    state_b = trainer_b.fit(
        train_batches, epochs=2, checkpoint_manager=manager_b,
        checkpoint_every=3, resume=True,
    )
    assert int(state_b.step) == int(state_a.step)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        state_a.params,
        state_b.params,
    )
    # optimizer state and rng resume exactly too
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        state_a.opt_state,
        state_b.opt_state,
    )
    np.testing.assert_array_equal(np.asarray(state_a.rng), np.asarray(state_b.rng))


@pytest.mark.jax
def test_resume_from_epoch_end_checkpoint(tmp_path):
    """Resume from an epoch-boundary checkpoint starts at the NEXT epoch."""

    def train_batches(epoch: int):
        return [make_batch(epoch * 10 + i) for i in range(3)]

    trainer_a = make_trainer()
    manager_a = CheckpointManager(str(tmp_path / "a"), max_to_keep=100)
    state_a = trainer_a.fit(train_batches, epochs=3, checkpoint_manager=manager_a)

    manager_b = CheckpointManager(str(tmp_path / "b"), max_to_keep=100)
    import shutil

    for step in manager_a.all_steps():
        if step <= 6:  # epochs 0 and 1 complete
            for suffix in (".npz", ".json"):
                src = (tmp_path / "a" / f"step_{step}").with_suffix(suffix)
                if src.exists():
                    shutil.copy(src, tmp_path / "b" / src.name)
    trainer_b = make_trainer()
    state_b = trainer_b.fit(
        train_batches, epochs=3, checkpoint_manager=manager_b, resume=True
    )
    assert int(state_b.step) == int(state_a.step)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        state_a.params,
        state_b.params,
    )


@pytest.mark.jax
def test_resume_requires_manager():
    trainer = make_trainer()
    with pytest.raises(ValueError, match="checkpoint_manager"):
        trainer.fit([make_batch(0)], resume=True)


@pytest.mark.jax
def test_resume_preserves_monitored_best(tmp_path):
    """A resumed run must not let a worse post-resume epoch steal best.json or
    the returned state: best_value is seeded from the restored history and the
    pre-kill best checkpoint wins when nothing beats it."""

    def scrambled_batch(seed: int) -> dict:
        # labels decoupled from inputs: unlearnable, so its loss stays HIGH
        batch = make_batch(seed)
        rng = np.random.default_rng(seed + 999)
        batch["positive_labels"] = rng.integers(
            0, NUM_ITEMS, batch["positive_labels"].shape
        ).astype(np.int32)
        return batch

    def train_batches(epoch: int):
        if epoch >= 2:  # the post-resume epoch is deliberately WORSE
            return [scrambled_batch(epoch * 10 + i) for i in range(3)]
        return [make_batch(epoch * 10 + i) for i in range(3)]

    # run 2 learnable epochs with the monitored best recorded on disk. LR 0.1:
    # at 1e-2 six steps barely move the loss off init, leaving it ABOVE the
    # scrambled epoch's ~log(NUM_ITEMS) random-label floor — the scenario's
    # "worse epoch" premise needs the learnable epochs to actually learn
    trainer_a = make_trainer(learning_rate=0.1)
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=100)
    trainer_a.fit(
        train_batches, epochs=2, checkpoint_manager=manager, monitor="train_loss",
        mode="min",
    )
    best_before = manager.best_step()
    best_loss_before = min(r["train_loss"] for r in trainer_a.history)

    # resume into the scrambled epoch: its loss is worse, so the pre-kill best
    # must survive both in best.json and as the returned state
    trainer_b = make_trainer(learning_rate=0.1)
    state_b = trainer_b.fit(
        train_batches, epochs=3, checkpoint_manager=manager, monitor="train_loss",
        mode="min", resume=True,
    )
    assert trainer_b.history[-1]["train_loss"] > best_loss_before
    assert manager.best_step() == best_before
    reference_best = manager.restore(state_b, step=best_before)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        reference_best.params,
        state_b.params,
    )


@pytest.mark.jax
def test_resume_monitored_best_survives_lost_history(tmp_path):
    """history.json lost (cleanup, torn filesystem): the monitored-best seed
    falls back to the best checkpoint's sidecar metadata — the same channel
    lr_scale resumes through — so a worse post-resume epoch still cannot
    repoint best.json or win the returned state."""

    def scrambled_batch(seed: int) -> dict:
        batch = make_batch(seed)
        rng = np.random.default_rng(seed + 999)
        batch["positive_labels"] = rng.integers(
            0, NUM_ITEMS, batch["positive_labels"].shape
        ).astype(np.int32)
        return batch

    def train_batches(epoch: int):
        if epoch >= 2:  # the post-resume epoch is deliberately worse
            return [scrambled_batch(epoch * 10 + i) for i in range(3)]
        return [make_batch(epoch * 10 + i) for i in range(3)]

    trainer_a = make_trainer(learning_rate=0.1)
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=100)
    trainer_a.fit(
        train_batches, epochs=2, checkpoint_manager=manager, monitor="train_loss",
        mode="min",
    )
    best_before = manager.best_step()
    (tmp_path / "run" / "history.json").unlink()  # the history record is gone
    assert manager.metadata(best_before)["train_loss"] is not None  # sidecar survives

    trainer_b = make_trainer(learning_rate=0.1)
    state_b = trainer_b.fit(
        train_batches, epochs=3, checkpoint_manager=manager, monitor="train_loss",
        mode="min", resume=True,
    )
    assert manager.best_step() == best_before
    reference_best = manager.restore(state_b, step=best_before)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        reference_best.params,
        state_b.params,
    )


@pytest.mark.jax
def test_resume_with_explicit_state_rejected(tmp_path):
    trainer = make_trainer()
    manager = CheckpointManager(str(tmp_path / "m"))
    state = trainer.init_state(make_batch(0))
    with pytest.raises(ValueError, match="ambiguous"):
        trainer.fit(
            [make_batch(0)], state=state, checkpoint_manager=manager, resume=True
        )


@pytest.mark.jax
def test_resume_already_complete_returns_checkpoint(tmp_path):
    def train_batches(epoch: int):
        return [make_batch(epoch * 10 + i) for i in range(3)]

    trainer_a = make_trainer()
    manager = CheckpointManager(str(tmp_path / "done"), max_to_keep=100)
    state_a = trainer_a.fit(train_batches, epochs=2, checkpoint_manager=manager)

    trainer_b = make_trainer()
    state_b = trainer_b.fit(
        train_batches, epochs=2, checkpoint_manager=manager, resume=True
    )
    assert int(state_b.step) == int(state_a.step)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        state_a.params,
        state_b.params,
    )


@pytest.mark.jax
def test_resume_when_best_step_points_at_deleted_step(tmp_path):
    """best.json referencing a step whose files were deleted (manual cleanup,
    over-eager retention) is stale, not fatal: best_step() returns None and a
    monitored resume completes, re-deriving the best from the restored
    history."""

    def train_batches(epoch: int):
        return [make_batch(epoch * 10 + i) for i in range(3)]

    trainer_a = make_trainer()
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=100)
    trainer_a.fit(
        train_batches, epochs=2, checkpoint_manager=manager, monitor="train_loss",
        mode="min",
    )
    best = manager.best_step()
    assert best is not None
    manager._delete_step(best)  # best.json now dangles
    assert manager.best_step() is None

    trainer_b = make_trainer()
    state_b = trainer_b.fit(
        train_batches, epochs=3, checkpoint_manager=manager, monitor="train_loss",
        mode="min", resume=True,
    )
    # the deleted best forced the resume back to the previous checkpoint, so
    # its epoch is replayed (one duplicate record); the run then completes
    assert trainer_b.history[-1]["epoch"] == 2
    assert np.isfinite(trainer_b.history[-1]["train_loss"])
    assert int(state_b.step) > 0
    assert manager.best_step() is not None  # a fresh best was re-marked


@pytest.mark.jax
def test_resume_after_interrupted_final_save(tmp_path):
    """A run whose final save was interrupted (truncated payload) resumes from
    the previous intact checkpoint and reproduces the uninterrupted final
    state exactly."""
    from replay_tpu.utils.faults import truncate_file

    def train_batches(epoch: int):
        return [make_batch(epoch * 10 + i) for i in range(3)]

    trainer_a = make_trainer()
    manager = CheckpointManager(str(tmp_path / "run"), max_to_keep=100)
    state_a = trainer_a.fit(train_batches, epochs=2, checkpoint_manager=manager)
    final = manager.latest_step()
    truncate_file(str(tmp_path / "run" / f"step_{final}.npz"), keep_fraction=0.5)

    assert manager.latest_step() == 3  # epoch-0 checkpoint survives the scan
    assert manager.skipped_steps == [final]
    trainer_b = make_trainer()
    state_b = trainer_b.fit(
        train_batches, epochs=2, checkpoint_manager=manager, resume=True
    )
    assert int(state_b.step) == int(state_a.step)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        state_a.params,
        state_b.params,
    )


@pytest.mark.jax
def test_resume_already_complete_returns_monitored_best(tmp_path):
    """When the finished run tracked a monitor, re-running with resume=True must
    hand back the BEST checkpoint (what the original fit returned), not the
    latest one."""

    def scrambled_batch(seed: int) -> dict:
        batch = make_batch(seed)
        rng = np.random.default_rng(seed + 999)
        batch["positive_labels"] = rng.integers(
            0, NUM_ITEMS, batch["positive_labels"].shape
        ).astype(np.int32)
        return batch

    def train_batches(epoch: int):
        if epoch >= 2:  # the final epoch is deliberately worse
            return [scrambled_batch(epoch * 10 + i) for i in range(3)]
        return [make_batch(epoch * 10 + i) for i in range(3)]

    # LR 0.1 (not the default 1e-2) so the learnable epochs genuinely beat the
    # scrambled epoch's random-label loss floor — see
    # test_resume_preserves_monitored_best
    trainer_a = make_trainer(learning_rate=0.1)
    manager = CheckpointManager(str(tmp_path / "done_best"), max_to_keep=100)
    state_a = trainer_a.fit(
        train_batches, epochs=3, checkpoint_manager=manager, monitor="train_loss",
        mode="min",
    )
    best_step = manager.best_step()
    assert best_step is not None and best_step != manager.latest_step()
    assert int(state_a.step) == best_step  # fit returned the best, not latest

    trainer_b = make_trainer(learning_rate=0.1)
    state_b = trainer_b.fit(
        train_batches, epochs=3, checkpoint_manager=manager, monitor="train_loss",
        mode="min", resume=True,
    )
    assert int(state_b.step) == best_step
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        state_a.params,
        state_b.params,
    )
