"""BERT4Rec: MLM training through the trainer, mask-append inference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.bert4rec import Bert4Rec
from replay_tpu.nn.transform import Compose
from replay_tpu.nn.transform.template import make_default_bert4rec_transforms

NUM_ITEMS = 12
SEQ_LEN = 8
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


def make_raw_batch(rng: np.random.Generator):
    """Cyclic next-item pattern (learnable bidirectionally)."""
    lengths = rng.integers(4, SEQ_LEN + 1, size=BATCH)
    items = np.full((BATCH, SEQ_LEN), NUM_ITEMS, dtype=np.int32)
    for b, n in enumerate(lengths):
        start = rng.integers(0, NUM_ITEMS)
        items[b, SEQ_LEN - n :] = (start + np.arange(n)) % NUM_ITEMS
    return {"item_id": items, "item_id_mask": items != NUM_ITEMS}


# this module's SharedPrograms, set by tests/conftest.py: one trainer, so nothing
# to share but the flax init under jax.jit
PROGRAMS = None


@pytest.fixture(scope="module")
def trained(schema):
    rng = np.random.default_rng(0)
    pipeline = Compose(make_default_bert4rec_transforms(schema, mask_prob=0.3)["train"])
    model = Bert4Rec(schema=schema, embedding_dim=16, num_blocks=1, num_heads=2,
                     max_sequence_length=SEQ_LEN)
    trainer = PROGRAMS.share_init(Trainer(
        model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2), mesh=make_mesh()
    ))
    key = jax.random.PRNGKey(0)
    state, losses = None, []
    raw_batches = [make_raw_batch(rng) for _ in range(6)]
    for epoch in range(20):
        for raw in raw_batches:
            key, sub = jax.random.split(key)
            batch = pipeline(dict(raw), sub)
            if state is None:
                state = trainer.init_state(batch)
            state, loss_value = trainer.train_step(state, batch)
            losses.append(float(loss_value))
    return trainer, state, losses, raw_batches


@pytest.mark.jax
def test_mlm_batch_contract(schema):
    rng = np.random.default_rng(1)
    raw = make_raw_batch(rng)
    batch = Compose(make_default_bert4rec_transforms(schema, mask_prob=0.3)["train"])(
        raw, jax.random.PRNGKey(1)
    )
    assert batch["positive_labels"].shape == (BATCH, SEQ_LEN, 1)
    assert batch["target_padding_mask"].shape == (BATCH, SEQ_LEN, 1)
    target = np.asarray(batch["target_padding_mask"][..., 0])
    token_mask = np.asarray(batch["token_mask"])
    padding = np.asarray(batch["padding_mask"])
    # targets are exactly the masked-out REAL positions
    np.testing.assert_array_equal(target, padding & ~token_mask)
    assert target.any()  # something is masked
    # token_mask is False somewhere real, and padding slots are never targets
    assert not target[~padding].any()


@pytest.mark.jax
def test_mlm_loss_decreases(trained):
    _, _, losses, _ = trained
    assert np.mean(losses[-12:]) < np.mean(losses[:12]) * 0.7


@pytest.mark.jax
def test_inference_shapes_and_quality(trained):
    trainer, state, _, raw_batches = trained
    raw = raw_batches[0]
    batch = {
        "feature_tensors": {"item_id": raw["item_id"]},
        "padding_mask": raw["item_id_mask"],
    }
    logits = trainer.predict_logits(state, batch)
    assert logits.shape == (BATCH, NUM_ITEMS)
    # candidate scoring agrees with full-catalog scoring
    candidates = jnp.array([0, 3, 7])
    restricted = trainer.predict_logits(state, batch, candidates)
    np.testing.assert_allclose(
        np.asarray(restricted), np.asarray(logits)[:, [0, 3, 7]], rtol=1e-5
    )
    # the learned cyclic pattern: true next item should rank in the top 3 usually
    last_real = raw["item_id"][np.arange(BATCH), -1]
    expected_next = (last_real + 1) % NUM_ITEMS
    top3 = np.asarray(jax.lax.top_k(logits, 3)[1])
    hit = np.mean([expected_next[b] in top3[b] for b in range(BATCH)])
    assert hit >= 0.5, f"top-3 hit rate {hit}"
