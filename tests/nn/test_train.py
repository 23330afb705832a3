"""Trainer end-to-end: SASRec trains through the template pipeline on the 8-device
CPU mesh (the reference's Lightning fit/validate/predict flow, SURVEY.md §3.2-3.3)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import (
    LRSchedulerFactory,
    OptimizerFactory,
    SeenItemsFilter,
    Trainer,
    make_mesh,
)
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.nn.transform import Compose
from replay_tpu.nn.transform.template import make_default_sasrec_transforms

NUM_ITEMS = 12
SEQ_LEN = 8
BATCH = 8  # divisible by the 8-device data axis

# this module's SharedPrograms, set by tests/conftest.py: every trainer's flax
# init runs under jax.jit; the trainers differ in loss, optimizer or dtype and
# keep their own programs, but for the one configuration two tests build
PROGRAMS = None


@pytest.fixture(scope="module")
def schema() -> TensorSchema:
    return TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            cardinality=NUM_ITEMS,
            embedding_dim=16,
        )
    )


def make_raw_batch(rng: np.random.Generator):
    """Left-padded sequences following a deterministic next-item pattern
    (item i -> item (i+1) % N) so the model has signal to learn."""
    lengths = rng.integers(3, SEQ_LEN + 1, size=BATCH)
    items = np.full((BATCH, SEQ_LEN), NUM_ITEMS, dtype=np.int32)
    for b, n in enumerate(lengths):
        start = rng.integers(0, NUM_ITEMS)
        items[b, SEQ_LEN - n :] = (start + np.arange(n)) % NUM_ITEMS
    mask = items != NUM_ITEMS
    return {"item_id": items, "item_id_mask": mask}


@pytest.fixture(scope="module")
def pipelines(schema):
    return {
        split: Compose(transforms)
        for split, transforms in make_default_sasrec_transforms(schema).items()
    }


@pytest.fixture(scope="module")
def trained(schema, pipelines):
    """Train a small SASRec for a few steps; shared across assertions below."""
    rng = np.random.default_rng(7)
    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1, num_heads=1,
                   max_sequence_length=SEQ_LEN)
    trainer = PROGRAMS.share_init(Trainer(
        model=model,
        loss=CE(),
        optimizer=OptimizerFactory(name="adam", learning_rate=5e-2),
        mesh=make_mesh(),
    ))
    batches = [pipelines["train"](make_raw_batch(rng)) for _ in range(6)]
    state = None
    losses = []
    for epoch in range(4):
        for batch in batches:
            if state is None:
                state = trainer.init_state(batch)
            state, loss_value = trainer.train_step(state, batch)
            losses.append(float(loss_value))
    return trainer, state, losses


@pytest.mark.jax
@pytest.mark.smoke
def test_loss_decreases(trained):
    _, _, losses = trained
    assert np.mean(losses[-6:]) < np.mean(losses[:6]) * 0.8


@pytest.mark.jax
def test_validate_metrics(trained, pipelines):
    trainer, state, _ = trained
    rng = np.random.default_rng(3)
    raw = make_raw_batch(rng)
    eval_batch = pipelines["validate"](dict(raw))
    # ground truth = the true next item of each sequence; train = seen items
    items = raw["item_id"]
    last = items[np.arange(BATCH), -1]
    gt = ((last + 1) % NUM_ITEMS)[:, None].astype(np.int32)
    eval_batch["ground_truth"] = gt
    eval_batch["train"] = np.where(raw["item_id_mask"], items, -1)
    metrics = trainer.validate(state, [eval_batch], metrics=("ndcg", "recall", "hitrate"),
                               top_k=(1, 5))
    assert set(metrics) == {"ndcg@1", "ndcg@5", "recall@1", "recall@5", "hitrate@1", "hitrate@5"}
    # the pattern is deterministic; a trained model should rank the true next item highly
    assert metrics["recall@5"] > 0.5
    assert 0.0 <= metrics["ndcg@5"] <= 1.0


@pytest.mark.jax
def test_predict_top_k_and_seen_filter(trained, pipelines):
    trainer, state, _ = trained
    rng = np.random.default_rng(5)
    raw = make_raw_batch(rng)
    batch = pipelines["predict"](dict(raw))
    batch["query_id"] = np.arange(BATCH)
    queries, items, scores = trainer.predict_top_k(state, [batch], k=4)
    assert items.shape == (BATCH, 4) and scores.shape == (BATCH, 4)
    assert (np.diff(scores, axis=1) <= 1e-6).all()  # ranked descending
    assert ((items >= 0) & (items < NUM_ITEMS)).all()
    # seen filter: no recommended item may appear in the query's history;
    # seen ids for the filter: the raw input sequence (padding redirected out of range)
    batch["seen_ids"] = np.where(raw["item_id_mask"], raw["item_id"], NUM_ITEMS)
    _, f_items, _ = trainer.predict_top_k(
        state, [batch], k=4, postprocessors=[SeenItemsFilter(seen_field="seen_ids")]
    )
    for b in range(BATCH):
        seen = set(raw["item_id"][b][raw["item_id_mask"][b]].tolist())
        assert not seen.intersection(f_items[b].tolist())


@pytest.mark.jax
def test_predict_dataframe(trained, pipelines):
    trainer, state, _ = trained
    rng = np.random.default_rng(11)
    raw = make_raw_batch(rng)
    batch = pipelines["predict"](dict(raw))
    batch["query_id"] = np.arange(100, 100 + BATCH)
    frame = trainer.predict_dataframe(state, [batch], k=3)
    assert list(frame.columns) == ["query_id", "item_id", "rating"]
    assert len(frame) == BATCH * 3
    assert set(frame["query_id"]) == set(range(100, 100 + BATCH))


@pytest.mark.jax
def test_candidates_restricted_scoring(trained, pipelines):
    trainer, state, _ = trained
    rng = np.random.default_rng(13)
    raw = make_raw_batch(rng)
    batch = pipelines["predict"](dict(raw))
    candidates = jnp.array([1, 3, 5])
    _, items, _ = trainer.predict_top_k(state, [batch], k=2, candidates=candidates)
    assert set(items.reshape(-1).tolist()) <= {1, 3, 5}


def test_scheduler_factories():
    for kind in ("constant", "step", "warmup_linear", "warmup_cosine"):
        schedule = LRSchedulerFactory(kind=kind, warmup_steps=5, total_steps=20).create(1e-3)
        assert np.isfinite(float(schedule(0))) and np.isfinite(float(schedule(10)))
    with pytest.raises(ValueError):
        LRSchedulerFactory(kind="nope").create(1e-3)
    with pytest.raises(ValueError):
        OptimizerFactory(name="nope").create()


@pytest.mark.jax
def test_bfloat16_training_smoke(schema, pipelines):
    """The bench configuration (bf16 compute dtype) trains to finite losses."""
    import jax.numpy as jnp

    rng = np.random.default_rng(17)
    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1,
                   max_sequence_length=SEQ_LEN, dtype=jnp.bfloat16)
    trainer = PROGRAMS.share_init(Trainer(model=model, loss=CE(),
                                          optimizer=OptimizerFactory(learning_rate=1e-2)))
    state, losses = None, []
    for _ in range(6):
        batch = pipelines["train"](make_raw_batch(rng))
        if state is None:
            state = trainer.init_state(batch)
        state, loss_value = trainer.train_step(state, batch)
        losses.append(float(loss_value))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    # parameters stay float32 (mixed precision: bf16 compute, f32 params)
    import jax
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(state.params))


@pytest.mark.jax
def test_sce_loss_through_trainer(schema, pipelines):
    """Large-catalog SCE loss plugs into the trainer and converges."""
    from replay_tpu.nn.loss import SCE, SCEParams

    rng = np.random.default_rng(23)
    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1,
                   max_sequence_length=SEQ_LEN)
    trainer = PROGRAMS.share_init(Trainer(
        model=model,
        loss=SCE(SCEParams(n_buckets=4, bucket_size_x=8, bucket_size_y=6)),
        optimizer=OptimizerFactory(learning_rate=2e-2),
    ))
    batches = [pipelines["train"](make_raw_batch(rng)) for _ in range(5)]
    state, losses = None, []
    for _ in range(6):
        for batch in batches:
            if state is None:
                state = trainer.init_state(batch)
            state, loss_value = trainer.train_step(state, batch)
            losses.append(float(loss_value))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    # the trained model still ranks the deterministic next item well
    raw = make_raw_batch(np.random.default_rng(29))
    logits = trainer.predict_logits(
        state, {"feature_tensors": {"item_id": raw["item_id"]},
                "padding_mask": raw["item_id_mask"]})
    assert logits.shape == (BATCH, NUM_ITEMS)


@pytest.mark.jax
def test_fit_multiple_validation_streams(schema, pipelines):
    """A dict of validation factories yields per-stream prefixed metrics
    (the reference's sequential CombinedLoader over several val paths)."""
    rng = np.random.default_rng(31)
    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = PROGRAMS.adopt(  # CE, lr 1e-2: one configuration, two tests
        Trainer(model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2))
    )

    def make_val():
        raw = make_raw_batch(rng)
        batch = pipelines["validate"](dict(raw))
        last = raw["item_id"][np.arange(BATCH), -1]
        batch["ground_truth"] = ((last + 1) % NUM_ITEMS)[:, None].astype(np.int32)
        return [batch]

    state = trainer.fit(
        lambda e: [pipelines["train"](make_raw_batch(rng))],
        epochs=1,
        val_batches={"val_a": make_val, "val_b": make_val},
        metrics=("recall",), top_k=(5,),
    )
    record = trainer.history[-1]
    assert "val_a/recall@5" in record and "val_b/recall@5" in record


@pytest.mark.jax
def test_monitor_early_stopping_and_best_state(schema, pipelines):
    """fit(monitor=..., patience=...) returns the BEST state and stops early."""
    rng = np.random.default_rng(41)
    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1, max_sequence_length=SEQ_LEN)
    # a big lr makes late epochs noisy, so train_loss (mode=min) has a real best
    trainer = PROGRAMS.adopt(  # CE, lr 1e-2: one configuration, two tests
        Trainer(model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2))
    )
    batches = [pipelines["train"](make_raw_batch(rng)) for _ in range(3)]
    state = trainer.fit(lambda e: batches, epochs=12, monitor="train_loss",
                        mode="min", patience=3)
    losses = [h["train_loss"] for h in trainer.history]
    best_epoch = int(np.argmin(losses))
    # stopped no later than best + patience
    assert len(losses) <= best_epoch + 1 + 3
    # the RETURNED state is the best epoch's snapshot: right step, live buffers
    assert int(state.step) == (best_epoch + 1) * 3
    assert np.isfinite(np.asarray(jax.tree.leaves(state.params)[0])).all()
    logits = trainer.predict_logits(
        state,
        {"feature_tensors": {"item_id": np.zeros((BATCH, SEQ_LEN), np.int32)},
         "padding_mask": np.ones((BATCH, SEQ_LEN), bool)},
    )
    assert logits.shape == (BATCH, NUM_ITEMS)
    with pytest.raises(KeyError, match="monitor"):
        trainer.fit(lambda e: batches, epochs=1, monitor="ndcg@10")
    with pytest.raises(ValueError, match="mode"):
        trainer.fit(lambda e: batches, epochs=1, monitor="train_loss", mode="sideways")


@pytest.mark.jax
@pytest.mark.parametrize("loss_name", ["CE", "CESampled", "BCE", "BCESampled",
                                       "LogInCE", "LogInCESampled", "LogOutCE", "SCE"])
def test_every_loss_trains_through_trainer(loss_name, schema, pipelines):
    """The trainer × loss matrix: every protocol loss runs a finite, decreasing
    training step stream on the same template batches."""
    from replay_tpu.nn import loss as loss_module
    from replay_tpu.nn.loss import SCE, SCEParams
    from replay_tpu.nn.transform import Compose, UniformNegativeSamplingTransform
    from replay_tpu.nn.transform.template import make_default_sasrec_transforms

    if loss_name == "SCE":
        loss = SCE(SCEParams(n_buckets=4, bucket_size_x=8, bucket_size_y=6))
    elif loss_name in ("LogInCE", "LogOutCE"):
        loss = getattr(loss_module, loss_name)(cardinality=NUM_ITEMS)
    else:
        loss = getattr(loss_module, loss_name)()
    sampled = "Sampled" in loss_name
    transforms = make_default_sasrec_transforms(schema)["train"]
    if sampled:
        transforms = transforms + [
            UniformNegativeSamplingTransform(cardinality=NUM_ITEMS, num_negative_samples=4)
        ]
    pipeline = Compose(transforms)
    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = PROGRAMS.share_init(
        Trainer(model=model, loss=loss, optimizer=OptimizerFactory(learning_rate=2e-2))
    )
    rng = np.random.default_rng(3)
    key = jax.random.PRNGKey(0)
    state, losses = None, []
    for _ in range(10):
        key, sub = jax.random.split(key)
        batch = pipeline(make_raw_batch(rng), sub if pipeline.needs_rng else None)
        if state is None:
            state = trainer.init_state(batch)
        state, loss_value = trainer.train_step(state, batch)
        losses.append(float(loss_value))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
