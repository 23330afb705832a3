"""Vocabulary surgery: catalog growth on trained parameters."""

import numpy as np
import pytest

import jax

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.nn.vocabulary import append_item_embeddings, resize_item_embeddings, set_item_embeddings

pytestmark = pytest.mark.jax

NUM_ITEMS, SEQ_LEN, BATCH = 8, 5, 4


def make_schema(cardinality=NUM_ITEMS):
    return TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=cardinality,
                          embedding_dim=8)
    )


def batch_of(items):
    """The next-item training batch of ``[BATCH, SEQ_LEN + 1]`` item ids."""
    mask = np.ones((BATCH, SEQ_LEN), bool)
    return {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }


def make_batch(num_items, rng):
    return batch_of(rng.integers(0, num_items, (BATCH, SEQ_LEN + 1)).astype(np.int32))


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer(model, optimizer=None) -> Trainer:
    """Each trainer keeps its own programs: the tests resize the vocabulary,
    which rebuilds them (and changes the schema the model holds). They share
    the jitted flax init."""
    return PROGRAMS.share_init(Trainer(
        model=model, loss=CE(),
        optimizer=optimizer or OptimizerFactory(learning_rate=1e-2),
    ))


def test_grow_shrink_and_replace():
    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    rng = np.random.default_rng(0)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), {"item_id": np.zeros((2, SEQ_LEN), np.int32)},
        np.ones((2, SEQ_LEN), bool),
    )["params"]
    params = jax.tree.map(np.asarray, params)
    old_table = params["body"]["embedder"]["embedding_item_id"]["table"]["embedding"].copy()

    grown = resize_item_embeddings(params, schema, NUM_ITEMS + 3)
    new_table = grown["body"]["embedder"]["embedding_item_id"]["table"]["embedding"]
    assert new_table.shape == (NUM_ITEMS + 4, 8)
    np.testing.assert_array_equal(new_table[:NUM_ITEMS], old_table[:NUM_ITEMS])
    np.testing.assert_array_equal(new_table[-1], old_table[-1])  # padding row moved last
    np.testing.assert_allclose(new_table[NUM_ITEMS], old_table[:NUM_ITEMS].mean(0), rtol=1e-6)
    assert schema["item_id"].cardinality == NUM_ITEMS + 3
    assert schema["item_id"].padding_value == NUM_ITEMS + 3

    appended = append_item_embeddings(grown, schema, np.ones((2, 8)))
    table2 = appended["body"]["embedder"]["embedding_item_id"]["table"]["embedding"]
    assert table2.shape == (NUM_ITEMS + 6, 8)
    np.testing.assert_array_equal(table2[NUM_ITEMS + 3], np.ones(8))

    replaced = set_item_embeddings(appended, schema, np.full((4, 8), 2.0))
    table3 = replaced["body"]["embedder"]["embedding_item_id"]["table"]["embedding"]
    assert table3.shape == (5, 8)
    assert schema["item_id"].cardinality == 4


def test_trainer_resize_then_train():
    """Growth mid-lifecycle: the resized state trains and scores the new items."""
    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = make_trainer(model)
    rng = np.random.default_rng(0)
    state = trainer.init_state(make_batch(NUM_ITEMS, rng))
    for _ in range(3):
        state, _ = trainer.train_step(state, make_batch(NUM_ITEMS, rng))

    new_items = NUM_ITEMS + 4
    state = trainer.resize_vocabulary(state, new_items)
    # trains on batches that contain the NEW item ids
    for _ in range(3):
        state, loss_value = trainer.train_step(state, make_batch(new_items, rng))
    assert np.isfinite(float(loss_value))
    logits = trainer.predict_logits(
        state,
        {"feature_tensors": {"item_id": np.zeros((2, SEQ_LEN), np.int32)},
         "padding_mask": np.ones((2, SEQ_LEN), bool)},
    )
    assert logits.shape == (2, new_items)

def test_reference_named_wrappers_and_old_logits_identical():
    """set_item_embeddings_by_size (xavier rows, ref lightning.py:507) and
    get_item_embeddings: after growth, OLD-item logits are bit-identical —
    inputs embed the same rows and the tied head's first columns are the
    untouched fitted rows."""
    from replay_tpu.nn.vocabulary import (
        get_item_embeddings,
        set_item_embeddings_by_size,
        set_item_embeddings_by_tensor,
    )

    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1,
                   max_sequence_length=SEQ_LEN)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, NUM_ITEMS, (3, SEQ_LEN)).astype(np.int32)
    mask = np.ones((3, SEQ_LEN), bool)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), {"item_id": ids}, mask)["params"]
    params = jax.tree.map(np.asarray, params)
    before = np.asarray(model.apply({"params": params}, {"item_id": ids}, mask,
                                    method=SasRec.forward_inference))
    fitted = get_item_embeddings(params, schema)
    assert fitted.shape == (NUM_ITEMS, 8)

    with pytest.raises(ValueError, match="greater"):
        set_item_embeddings_by_size(params, schema, NUM_ITEMS)
    grown = set_item_embeddings_by_size(params, schema, NUM_ITEMS + 5,
                                        rng=jax.random.PRNGKey(7))
    grown_model = SasRec(schema=schema, embedding_dim=8, num_blocks=1,
                         max_sequence_length=SEQ_LEN)
    after = np.asarray(grown_model.apply({"params": grown}, {"item_id": ids}, mask,
                                         method=SasRec.forward_inference))
    assert after.shape == (3, NUM_ITEMS + 5)
    np.testing.assert_array_equal(after[:, :NUM_ITEMS], before)
    new_rows = get_item_embeddings(grown, schema)[NUM_ITEMS:]
    assert np.abs(new_rows).max() > 0  # xavier, not zeros
    assert not np.allclose(new_rows, fitted.mean(0))  # NOT the mean-init path

    replacement = np.full((NUM_ITEMS + 5, 8), 2.0, np.float32)
    replaced = set_item_embeddings_by_tensor(grown, schema, replacement)
    np.testing.assert_array_equal(get_item_embeddings(replaced, schema), replacement)


def test_bert4rec_surgery_and_warm_start_state():
    """Surgery works on Bert4Rec too, and Trainer.init_state(params=...) seeds
    a fresh optimizer around existing weights (the retrain-after-surgery flow
    without Trainer.resize_vocabulary)."""
    from replay_tpu.nn.sequential.bert4rec import Bert4Rec
    from replay_tpu.nn.vocabulary import get_item_embeddings, set_item_embeddings_by_size

    schema = make_schema()
    model = Bert4Rec(schema=schema, embedding_dim=8, num_blocks=1, num_heads=2,
                     max_sequence_length=SEQ_LEN)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), {"item_id": np.zeros((2, SEQ_LEN), np.int32)},
        np.ones((2, SEQ_LEN), bool),
    )["params"]
    params = jax.tree.map(np.asarray, params)
    grown = set_item_embeddings_by_size(params, schema, NUM_ITEMS + 2)
    assert get_item_embeddings(grown, schema).shape == (NUM_ITEMS + 2, 8)

    new_model = Bert4Rec(schema=schema, embedding_dim=8, num_blocks=1, num_heads=2,
                         max_sequence_length=SEQ_LEN)
    trainer = make_trainer(new_model, OptimizerFactory(name="sgd", learning_rate=0.1))
    rng = np.random.default_rng(1)
    batch = make_batch(NUM_ITEMS + 2, rng)
    state = trainer.init_state(batch, params=grown)
    np.testing.assert_array_equal(
        get_item_embeddings(jax.tree.map(np.asarray, state.params), schema),
        get_item_embeddings(grown, schema),
    )
    losses = []
    for _ in range(6):
        state, loss_value = trainer.train_step(state, batch)
        losses.append(float(loss_value))
    assert losses[-1] < losses[0]


# --------------------------------------------------------------------------- #
# optimizer-state-safe surgery (continual training, docs/robustness.md)
# --------------------------------------------------------------------------- #
def _item_moments(opt_state):
    """Every optimizer-state leaf mirroring the item table, as numpy."""
    from replay_tpu.nn.vocabulary import _find_moment_leaves

    return [
        np.asarray(leaf)
        for _, leaf in _find_moment_leaves(
            jax.tree.map(np.asarray, opt_state), "item_id"
        )
    ]


def _trained_state(trainer, rng, steps=3, num_items=NUM_ITEMS):
    state = trainer.init_state(make_batch(num_items, rng))
    for _ in range(steps):
        state, _ = trainer.train_step(state, make_batch(num_items, rng))
    return state


def test_resize_vocabulary_carries_adam_moments_in_lockstep():
    """Mid-run growth: trained rows keep their mu/nu, cold rows start at
    zero, the padding row's moments move to the new end with it."""
    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = make_trainer(model)
    state = _trained_state(trainer, np.random.default_rng(0))
    before = _item_moments(state.opt_state)
    assert len(before) >= 2  # adam: mu and nu at least
    assert any(np.abs(m).max() > 0 for m in before)  # the moments are TRAINED

    grown = trainer.resize_vocabulary(state, NUM_ITEMS + 4)  # carry_opt_state default
    after = _item_moments(grown.opt_state)
    assert len(after) == len(before)
    for old, new in zip(before, after):
        assert new.shape == (NUM_ITEMS + 5, 8)
        np.testing.assert_array_equal(new[:NUM_ITEMS], old[:NUM_ITEMS])
        np.testing.assert_array_equal(new[NUM_ITEMS:-1], 0.0)  # cold rows: fresh
        np.testing.assert_array_equal(new[-1], old[-1])  # padding moments moved last
    # step/rng carry over and the state still trains on the new ids
    rng = np.random.default_rng(7)
    grown, loss_value = trainer.train_step(grown, make_batch(NUM_ITEMS + 4, rng))
    assert np.isfinite(float(loss_value))


def test_resize_item_embeddings_opt_state_roundtrip_and_out_of_sync_guard():
    from replay_tpu.nn.vocabulary import resize_optimizer_state

    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = make_trainer(model)
    state = _trained_state(trainer, np.random.default_rng(1))
    params = jax.tree.map(np.asarray, state.params)
    opt_host = jax.tree.map(np.asarray, state.opt_state)

    params2, opt2 = resize_item_embeddings(
        params, schema, NUM_ITEMS + 2, opt_state=opt_host
    )
    table = params2["body"]["embedder"]["embedding_item_id"]["table"]["embedding"]
    assert table.shape == (NUM_ITEMS + 3, 8)
    for moment in _item_moments(opt2):
        assert moment.shape == (NUM_ITEMS + 3, 8)

    # resizing AGAIN with the schema already moved but the OLD opt state is
    # the out-of-sync case: the error names the path, not an optax traceback
    with pytest.raises(ValueError, match="out of sync"):
        resize_optimizer_state(opt_host, "item_id", NUM_ITEMS + 2, NUM_ITEMS + 4)


def test_fit_rejects_resumed_state_with_stale_opt_state():
    """The satellite guard: params grown without their moments must fail at
    fit start with an error NAMING the table path."""
    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = make_trainer(model)
    rng = np.random.default_rng(2)
    state = _trained_state(trainer, rng)
    grown_params = resize_item_embeddings(
        jax.tree.map(np.asarray, state.params), schema, NUM_ITEMS + 4
    )
    stale = state.replace(params=grown_params)  # opt_state NOT resized
    with pytest.raises(ValueError, match="embedding_item_id"):
        trainer.fit([make_batch(NUM_ITEMS + 4, rng)], epochs=1, state=stale)


def test_validate_optimizer_state_passes_on_consistent_pair():
    from replay_tpu.nn.vocabulary import validate_optimizer_state

    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = make_trainer(model)
    state = _trained_state(trainer, np.random.default_rng(3), steps=1)
    validate_optimizer_state(state.params, state.opt_state, schema)  # no raise
    grown = trainer.resize_vocabulary(state, NUM_ITEMS + 4)
    validate_optimizer_state(grown.params, grown.opt_state, schema)  # still in sync


def test_finetune_entry_grows_then_fits_from_trained_state():
    """Trainer.finetune: the continual-training seam — optional xavier-grown
    catalog, optimizer moments carried, then a plain fit on the fresh tail."""
    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = make_trainer(model)
    rng = np.random.default_rng(4)
    state = _trained_state(trainer, rng)
    old_table = np.asarray(
        jax.tree.map(np.asarray, state.params)
        ["body"]["embedder"]["embedding_item_id"]["table"]["embedding"]
    ).copy()

    tail = [make_batch(NUM_ITEMS + 4, rng) for _ in range(2)]
    tuned = trainer.finetune(state, tail, new_cardinality=NUM_ITEMS + 4)
    table = np.asarray(
        jax.tree.map(np.asarray, tuned.params)
        ["body"]["embedder"]["embedding_item_id"]["table"]["embedding"]
    )
    assert table.shape == (NUM_ITEMS + 5, 8)
    assert schema["item_id"].cardinality == NUM_ITEMS + 4
    # the fit actually trained (params moved) and shrink is refused
    assert np.abs(table[:NUM_ITEMS] - old_table[:NUM_ITEMS]).max() > 0
    with pytest.raises(ValueError, match="shrink"):
        trainer.finetune(tuned, tail, new_cardinality=NUM_ITEMS)


def test_continual_stream_absorbs_catalog_growth_and_scores_next_day():
    """The continual loop end to end, in process: ONE model rides a stream whose
    catalog grows mid-way (``fit`` on day 0, ``finetune(new_cardinality=...)`` on
    day 1's tail) and is scored prequentially on day 2's events, whose true next
    items include ids that did not exist on day 0."""
    grown_items, top_k = NUM_ITEMS + 4, 3

    def walks(catalog, rng, count):
        """[count, BATCH, SEQ_LEN + 2] successor walks (+5 mod the day's catalog):
        learnable, and the cold ids enter the pattern the day they appear."""
        starts = rng.integers(0, catalog, (count, BATCH, 1))
        return ((starts + 5 * np.arange(SEQ_LEN + 2)) % catalog).astype(np.int32)

    schema = make_schema()
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    trainer = make_trainer(model)
    rng = np.random.default_rng(0)
    head = [batch_of(walk[:, :SEQ_LEN + 1]) for walk in walks(NUM_ITEMS, rng, 6)]
    state = trainer.fit(head, epochs=2, log_every=0)
    tail = [batch_of(walk[:, :SEQ_LEN + 1]) for walk in walks(grown_items, rng, 6)]
    state = trainer.finetune(state, tail, new_cardinality=grown_items, epochs=2, log_every=0)
    assert schema["item_id"].cardinality == grown_items

    next_day = [
        {
            "feature_tensors": {"item_id": walk[:, 1:SEQ_LEN + 1]},
            "padding_mask": np.ones((BATCH, SEQ_LEN), bool),
            "ground_truth": walk[:, SEQ_LEN + 1:],
        }
        for walk in walks(grown_items, rng, 3)
    ]
    assert max(int(batch["ground_truth"].max()) for batch in next_day) >= NUM_ITEMS
    scores = trainer.validate(state, next_day, metrics=("ndcg", "recall"), top_k=(top_k,))
    ndcg, recall = scores[f"ndcg@{top_k}"], scores[f"recall@{top_k}"]
    assert np.isfinite(ndcg) and 0.0 < ndcg <= 1.0
    # the continued model learned the GROWN catalog's pattern: well above the
    # top_k / grown_items a model that ignored the tail would score
    assert recall > top_k / grown_items
