"""TwoTower: in-batch-negative training, catalog scoring vs brute force, reader."""

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer
from replay_tpu.nn.loss import CESampled
from replay_tpu.nn.sequential.twotower import FeaturesReader, TwoTower
from replay_tpu.nn.transform import Compose
from replay_tpu.nn.transform.template import make_default_twotower_transforms

NUM_ITEMS = 12
SEQ_LEN = 6
BATCH = 8


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


@pytest.fixture(scope="module")
def item_schema() -> TensorSchema:
    return TensorSchema(
        TensorFeatureInfo("category", FeatureType.CATEGORICAL, cardinality=3, embedding_dim=16)
    )


@pytest.fixture(scope="module")
def item_feature_tensors():
    return {"category": (np.arange(NUM_ITEMS) % 3).astype(np.int32)}


def make_raw_batch(rng: np.random.Generator):
    items = np.full((BATCH, SEQ_LEN), NUM_ITEMS, dtype=np.int32)
    for b in range(BATCH):
        n = rng.integers(3, SEQ_LEN + 1)
        start = rng.integers(0, NUM_ITEMS)
        items[b, SEQ_LEN - n :] = (start + np.arange(n)) % NUM_ITEMS
    return {"item_id": items, "item_id_mask": items != NUM_ITEMS}


# this module's SharedPrograms, set by tests/conftest.py: the two trainers are of
# two models and keep their programs; their flax init runs under jax.jit
PROGRAMS = None


@pytest.fixture(scope="module")
def trained(schema, item_schema, item_feature_tensors):
    rng = np.random.default_rng(0)
    pipeline = Compose(make_default_twotower_transforms(schema)["train"])
    model = TwoTower(schema=schema, item_schema=item_schema, embedding_dim=16,
                     num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = PROGRAMS.share_init(Trainer(model=model, loss=CESampled(),
                                          optimizer=OptimizerFactory(learning_rate=1e-2)))
    state, losses = None, []
    raws = [make_raw_batch(rng) for _ in range(6)]
    for _ in range(10):
        for raw in raws:
            batch = pipeline(dict(raw))
            batch["item_feature_tensors"] = item_feature_tensors
            if state is None:
                state = trainer.init_state(batch)
            state, loss_value = trainer.train_step(state, batch)
            losses.append(float(loss_value))
    return trainer, state, losses, raws


@pytest.mark.jax
def test_template_emits_in_batch_negatives(schema):
    raw = make_raw_batch(np.random.default_rng(1))
    batch = Compose(make_default_twotower_transforms(schema)["train"])(raw)
    negatives = np.asarray(batch["negative_labels"])
    positives = np.asarray(batch["positive_labels"])
    assert negatives.shape == (BATCH,)
    np.testing.assert_array_equal(negatives, positives[:, -1, 0])


@pytest.mark.jax
def test_in_batch_training_loss_decreases(trained):
    _, _, losses, _ = trained
    assert np.mean(losses[-6:]) < np.mean(losses[:6]) * 0.9


@pytest.mark.jax
def test_retrieval_matches_brute_force(trained, item_feature_tensors):
    """Top-k through forward_inference must equal brute-force query·item scores."""
    trainer, state, _, raws = trained
    raw = raws[0]
    batch = {
        "feature_tensors": {"item_id": raw["item_id"]},
        "padding_mask": raw["item_id_mask"],
        "item_feature_tensors": item_feature_tensors,
    }
    logits = np.asarray(trainer.predict_logits(state, batch))
    assert logits.shape == (BATCH, NUM_ITEMS)

    model = trainer.model
    queries = model.apply(
        {"params": state.params},
        batch["feature_tensors"],
        batch["padding_mask"],
        method=TwoTower.get_query_embeddings,
    )
    items = model.apply(
        {"params": state.params},
        item_feature_tensors=item_feature_tensors,
        method=TwoTower.encode_items,
    )
    brute = np.asarray(queries) @ np.asarray(items).T
    np.testing.assert_allclose(logits, brute, rtol=1e-4, atol=1e-5)
    # and top-k selection agrees
    np.testing.assert_array_equal(
        np.asarray(jax.lax.top_k(jnp.asarray(logits), 3)[1]),
        np.asarray(jax.lax.top_k(jnp.asarray(brute), 3)[1]),
    )


@pytest.mark.jax
def test_item_features_change_scores(trained, item_feature_tensors):
    """The fused catalog features must actually influence the item tower."""
    trainer, state, _, raws = trained
    raw = raws[0]
    base = {
        "feature_tensors": {"item_id": raw["item_id"]},
        "padding_mask": raw["item_id_mask"],
        "item_feature_tensors": item_feature_tensors,
    }
    shuffled = dict(base)
    shuffled["item_feature_tensors"] = {
        "category": ((np.arange(NUM_ITEMS) + 1) % 3).astype(np.int32)
    }
    a = np.asarray(trainer.predict_logits(state, base))
    b = np.asarray(trainer.predict_logits(state, shuffled))
    assert not np.allclose(a, b)


def test_features_reader():
    item_schema = TensorSchema(
        TensorFeatureInfo("category", FeatureType.CATEGORICAL, cardinality=3, embedding_dim=8)
    )
    frame = pd.DataFrame({"item_id": [2, 0, 1], "category": [2, 0, 1]})
    tensors = FeaturesReader(item_schema, num_items=4).read(frame)
    np.testing.assert_array_equal(tensors["category"], [0, 1, 2, 0])  # id 3 missing -> 0
    with pytest.raises(ValueError, match="Duplicate"):
        FeaturesReader(item_schema).read(pd.DataFrame({"item_id": [0, 0], "category": [1, 2]}))
    with pytest.raises(ValueError, match="encoded"):
        FeaturesReader(item_schema, num_items=2).read(
            pd.DataFrame({"item_id": [0, 5], "category": [1, 2]})
        )


@pytest.mark.jax
def test_predict_uses_cached_catalog(trained, item_feature_tensors):
    """predict_top_k's cached-catalog path returns the same ranking as
    per-batch forward_inference, and encodes the catalog only once."""
    trainer, state, _, raws = trained
    raw = raws[0]
    batch = {
        "feature_tensors": {"item_id": raw["item_id"]},
        "padding_mask": raw["item_id_mask"],
        "item_feature_tensors": item_feature_tensors,
        "query_id": np.arange(BATCH),
    }
    _, items_cached, scores_cached = trainer.predict_top_k(state, [dict(batch)], k=4)
    per_batch = np.asarray(trainer.predict_logits(state, dict(batch)))
    order = np.argsort(-per_batch, axis=1)[:, :4]
    np.testing.assert_array_equal(items_cached, order)
    np.testing.assert_allclose(
        scores_cached, np.take_along_axis(per_batch, order, 1), rtol=1e-4, atol=1e-5
    )
    calls = {"n": 0}
    original = trainer._catalog_fn

    def counting(params, features):
        calls["n"] += 1
        return original(params, features)

    trainer._catalog_fn = counting
    trainer.predict_top_k(state, [dict(batch), dict(batch), dict(batch)], k=4)
    trainer._catalog_fn = original
    assert calls["n"] == 1  # one catalog encode for three batches


class _GateMerger(__import__("flax").linen.Module):
    """Context merger: gates the hidden state by a learned projection of the
    last item id embedding-index parity (a minimal ContextMergerProto)."""

    @__import__("flax").linen.compact
    def __call__(self, hidden, feature_tensors):
        import flax.linen as nn
        import jax.numpy as jnp

        signal = (feature_tensors["item_id"] % 2).astype(hidden.dtype)[..., None]
        gate = nn.Dense(hidden.shape[-1], name="gate")(signal)
        return hidden * jax.nn.sigmoid(gate)


def test_context_merger_changes_outputs_and_trains(schema):
    """context_merger (ref model.py:431,516) fuses input features into the
    query hidden states in BOTH training and inference paths."""
    rng = np.random.default_rng(3)
    batch = make_raw_batch(rng)
    plain = TwoTower(schema=schema, embedding_dim=16, max_sequence_length=SEQ_LEN)
    merged = TwoTower(
        schema=schema, embedding_dim=16, max_sequence_length=SEQ_LEN,
        context_merger=_GateMerger(),
    )
    feats = {"item_id": batch["item_id"]}
    mask = batch["item_id_mask"]
    # init through forward_inference so BOTH towers' params are created
    p_plain = plain.init(jax.random.PRNGKey(0), feats, mask, method=TwoTower.forward_inference)
    p_merged = merged.init(jax.random.PRNGKey(0), feats, mask, method=TwoTower.forward_inference)
    # the merger registers its own parameters
    assert "context_merger" in p_merged["params"]
    out_plain = plain.apply(p_plain, feats, mask)
    out_merged = merged.apply(p_merged, feats, mask)
    assert out_plain.shape == out_merged.shape
    assert not np.allclose(np.asarray(out_plain), np.asarray(out_merged))
    # inference path goes through the merger too
    scores = merged.apply(p_merged, feats, mask, method=TwoTower.forward_inference)
    assert scores.shape == (BATCH, NUM_ITEMS)
    # and it trains end-to-end through the shared Trainer
    trainer = PROGRAMS.share_init(Trainer(
        model=merged,
        loss=CESampled(),
        optimizer=OptimizerFactory(learning_rate=1e-2),
    ))
    pipeline = Compose(make_default_twotower_transforms(schema)["train"])
    state, losses = None, []
    for i in range(4):
        batch = pipeline(dict(make_raw_batch(np.random.default_rng(i))))
        if state is None:
            state = trainer.init_state(batch)
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
